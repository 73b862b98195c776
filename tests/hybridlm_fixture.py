"""A tiny hybrid linear-attention model (``models/hybridlm.py``) and the
configuration keys its builder and its plain reference read, for the
tests (not a test file)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from looplm_fixture import SGD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, HIDDEN, CAP = 256, 64, 24


def config(experts=8, held=2, first=0, layers=5, top_k=2):
    """One period and the dense layer in front (KDA+dense, KDA+MoE,
    KDA+MoE, MLA+MoE, KDA+MoE): hidden 64, 2 KDA heads of 16, ``experts``
    routed experts of which ``held`` are here, vocabulary 256."""
    return {
        "hidden_size": HIDDEN, "num_hidden_layers": layers,
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "linear_attn_config": {
            "kda_layers": [1, 2, 3, 5, 6, 7, 9], "full_attn_layers": [4, 8],
            "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4},
        "num_attention_heads": 2, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "num_experts": held, "num_experts_per_token": top_k,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "routed_scaling_factor": 2.446, "vocab_size": VOCAB,
        "rms_norm_eps": 1e-5,
        "published": {"num_experts": experts},
        "share": {"expert_first": first},
        "kda": {"gate_rank": 8},
        "loss": {"init_std": 0.02, "key_base": 1, "negative_seed": 11},
        "table": {"embedx_dim": HIDDEN, "sgd": dict(SGD)}}


def model_of(cfg):
    spec = importlib.util.spec_from_file_location(
        "benchmark_models_kimi_linear_48b",
        os.path.join(ROOT, "benchmark", "models", "kimi_linear_48b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build(cfg)


def seeded(cfg, seed=0, lengths=(CAP, 13, 1), rows=60):
    """Model, parameters (norm gains, the router's and the gates' inputs
    moved off their starts), a table of created rows, and one batch with
    padded tails and a length-1 sequence, as the model and as the
    reference read it."""
    model = model_of(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for w in params["layers"]:
        for g in ("g1", "g2"):
            w[g] = w[g] + 0.1 * jax.random.normal(next(noise), w[g].shape)
        if "router" in w["ffn"]:     # scores apart, so no top-k is a tie
            w["ffn"]["router"] = 2.5 * w["ffn"]["router"]
    rng = np.random.default_rng(seed)
    b = len(lengths)
    ln = np.array([lengths], np.int32)
    mask = np.arange(CAP)[None, :, None] < ln[:, None, :]
    idx = np.where(mask, rng.integers(1, rows, (1, CAP, b)), 0
                   ).astype(np.int32)
    mf = rng.normal(0, 0.05, (rows, HIDDEN)).astype(np.float32)
    mf[0] = 0
    key_of_row = rng.integers(1, cfg["vocab_size"] + 1, rows)
    seq_keys = np.where(mask[0].T, key_of_row[idx[0].T], 0).astype(np.int32)
    batch = {"indices": idx, "lengths": ln, "valid": np.ones(b, bool),
             "seq_keys": seq_keys, "labels": np.zeros(b, np.float32)}
    args = (jnp.asarray(mf)[idx[0].T][:, None], jnp.asarray(ln.T),
            jnp.ones(b, bool), jnp.asarray(seq_keys))
    return model, params, mf, batch, args
