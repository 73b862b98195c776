"""The program's DeepFM at this configuration's widths."""


def build(cfg: dict):
    from paddlebox_tpu.models.deepfm import DeepFM
    return DeepFM(num_slots=len(cfg["fields"]["vocab"]),
                  emb_width=3 + cfg["table"]["embedx_dim"],
                  dense_dim=cfg["fields"]["dense_dim"],
                  hidden=tuple(cfg["model"]["hidden"]))
