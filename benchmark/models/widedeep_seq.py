"""The program's Wide&Deep at this configuration's widths."""


def build(cfg: dict):
    from paddlebox_tpu.models.widedeep import WideDeep
    return WideDeep(num_slots=len(cfg["fields"]["vocab"]),
                    emb_width=3 + cfg["table"]["embedx_dim"],
                    dense_dim=cfg["fields"]["dense_dim"],
                    hidden=tuple(cfg["model"]["hidden"]))
