"""The program's looped language model at this configuration's sizes."""


def build(cfg: dict):
    from paddlebox_tpu.models.looplm import LoopLM
    return LoopLM(hidden=cfg["hidden_size"],
                  heads=cfg["num_attention_heads"],
                  head_dim=cfg["head_dim"], ffn=cfg["intermediate_size"],
                  layers=cfg["num_hidden_layers"],
                  ut_steps=cfg["total_ut_steps"], vocab=cfg["vocab_size"],
                  rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
                  beta=cfg["loss"]["beta"],
                  init_std=cfg["loss"]["init_std"],
                  key_base=cfg["loss"]["key_base"],
                  neg_seed=cfg["loss"]["negative_seed"])
