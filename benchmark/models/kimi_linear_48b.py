"""The program's hybrid linear-attention model at this configuration's
sizes and share: the layer kinds from ``linear_attn_config`` and
``first_k_dense_replace`` (``harness/flops_hybrid.py::layer_kinds``, which
counts the same layers), the router as wide as the published expert
count, the experts held here ``share.expert_first`` on."""


def build(cfg: dict):
    from benchmark.harness.flops_hybrid import layer_kinds
    from paddlebox_tpu.models.hybridlm import HybridLM
    lin = cfg["linear_attn_config"]
    first = cfg["share"]["expert_first"]
    return HybridLM(
        hidden=cfg["hidden_size"], layers=layer_kinds(cfg),
        vocab=cfg["vocab_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        gate_rank=cfg["kda"]["gate_rank"],
        mla_heads=cfg["num_attention_heads"], kv_rank=cfg["kv_lora_rank"],
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], ffn=cfg["intermediate_size"],
        experts=cfg["published"]["num_experts"],
        experts_held=range(first, first + cfg["num_experts"]),
        top_k=cfg["num_experts_per_token"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        eps=cfg["rms_norm_eps"], init_std=cfg["loss"]["init_std"],
        key_base=cfg["loss"]["key_base"],
        neg_seed=cfg["loss"]["negative_seed"])
