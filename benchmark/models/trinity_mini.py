"""The program's AFMoE model at this configuration's sizes and share: the
layer kinds of the published layers held here from ``layer_types`` and
``num_dense_layers`` (``harness/flops_afmoe.py::layer_kinds``, which
counts the same layers), the router as wide as the published expert
count, the experts held here ``share.expert_first`` on, the muP input
scale where ``mup_enabled`` says so and the bias rule at
``load_balance_coeff``."""

import math


def build(cfg: dict):
    from benchmark.harness.flops_afmoe import layer_kinds
    from paddlebox_tpu.models.afmoe import AfmoeLM
    first = cfg["share"]["expert_first"]
    hidden = cfg["hidden_size"]
    return AfmoeLM(
        hidden=hidden, layers=layer_kinds(cfg), vocab=cfg["vocab_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        rope_theta=cfg["rope_theta"], ffn=cfg["intermediate_size"],
        experts=cfg["published"]["num_experts"],
        experts_held=range(first, first + cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_experts=cfg["num_shared_experts"],
        routed_scale=cfg["route_scale"],
        balance_rate=cfg["load_balance_coeff"],
        input_scale=math.sqrt(hidden) if cfg["mup_enabled"] else 1.0,
        eps=cfg["rms_norm_eps"], init_std=cfg["loss"]["init_std"],
        key_base=cfg["loss"]["key_base"],
        neg_seed=cfg["loss"]["negative_seed"])
