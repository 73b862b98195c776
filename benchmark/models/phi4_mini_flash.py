"""The program's decoder-hybrid-decoder model at this configuration's
sizes and share: the layer kinds from the published indices of the layers
held here (``harness/flops_sambay.py::layer_kinds``, which counts the same
layers), every width as the configuration states it, the head the table's
rows over the held vocabulary slice."""


def build(cfg: dict):
    from benchmark.harness.flops_sambay import layer_kinds, sambay_sizes
    from paddlebox_tpu.models.sambay import SambaYLM
    sz = sambay_sizes(cfg)
    return SambaYLM(
        hidden=sz["hidden"], layers=layer_kinds(cfg), vocab=sz["vocab"],
        heads=sz["heads"], kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        window=sz["window"], ffn=sz["ffn"], d_inner=sz["d_inner"],
        d_state=sz["d_state"], dt_rank=sz["dt_rank"],
        conv_kernel=sz["conv_kernel"], eps=cfg["layer_norm_eps"],
        init_std=cfg["loss"]["init_std"], key_base=cfg["loss"]["key_base"],
        neg_seed=cfg["loss"]["negative_seed"])
