"""A tiny AFMoE model (``models/afmoe.py``) and the configuration keys that
``benchmark/models/trinity_mini.py::build`` and the plain reference read,
for the tests (not a test file)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from looplm_fixture import SGD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, HIDDEN, CAP, WINDOW = 256, 64, 24, 6
# the published pattern: three sliding layers, then a full one
LAYER_TYPES = (["sliding_attention"] * 3 + ["full_attention"]) * 8


def config(experts=8, held=2, first=0, layers=5, top_k=2, window=WINDOW):
    """Published layers 1-5 (dense on a sliding layer, then routed on
    sliding, full, sliding, sliding): hidden 64, 4 query heads of 16 on 2
    kv heads, ``experts`` routed experts of which ``held`` are here,
    vocabulary 256."""
    return {
        "hidden_size": HIDDEN, "num_hidden_layers": layers,
        "num_dense_layers": 2, "intermediate_size": 96,
        "layer_types": list(LAYER_TYPES), "sliding_window": window,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rope_theta": 10000.0, "mup_enabled": True,
        "num_experts": held, "num_experts_per_tok": top_k,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "route_scale": 2.826, "load_balance_coeff": 0.001,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-5,
        "published": {"num_experts": experts},
        "share": {"layer_first": 1, "expert_first": first},
        "loss": {"init_std": 0.02, "key_base": 1, "negative_seed": 13},
        "table": {"embedx_dim": HIDDEN, "sgd": dict(SGD)}}


def model_of(cfg):
    spec = importlib.util.spec_from_file_location(
        "benchmark_models_trinity_mini",
        os.path.join(ROOT, "benchmark", "models", "trinity_mini.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build(cfg)


def seeded(cfg, seed=0, lengths=(CAP, 13, 1), rows=60):
    """Model, parameters (the norm gains moved off their starts, the
    router's scores apart so that no top-k is a tie, a bias that is not
    zero), a table of rows, and one batch with padded tails and a
    length-1 sequence, as the model and as the reference read it."""
    model = model_of(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))
    for w in params["layers"]:
        for g in ("g1", "g1_post", "g2", "g2_post"):
            w[g] = w[g] + 0.1 * jax.random.normal(next(noise), w[g].shape)
        for g in ("g_q", "g_k"):
            w["mixer"][g] = w["mixer"][g] + 0.1 * jax.random.normal(
                next(noise), w["mixer"][g].shape)
        if "router" in w["ffn"]:
            w["ffn"]["router"] = 2.5 * w["ffn"]["router"]
            w["ffn"]["router_bias"] = 0.01 * jax.random.normal(
                next(noise), w["ffn"]["router_bias"].shape)
    rng = np.random.default_rng(seed)
    b = len(lengths)
    ln = np.array([lengths], np.int32)
    mask = np.arange(CAP)[None, :, None] < ln[:, None, :]
    idx = np.where(mask, rng.integers(1, rows, (1, CAP, b)), 0
                   ).astype(np.int32)
    # rows centred (the table's start, uniform in [0, 0.04), herds every
    # token to the same experts, and a held expert no token chose gives
    # the router no gradient to compare)
    mf = rng.normal(0, 0.05, (rows, HIDDEN)).astype(np.float32)
    mf[0] = 0
    key_of_row = rng.integers(1, cfg["vocab_size"] + 1, rows)
    seq_keys = np.where(mask[0].T, key_of_row[idx[0].T], 0).astype(np.int32)
    batch = {"indices": idx, "lengths": ln, "valid": np.ones(b, bool),
             "seq_keys": seq_keys, "labels": np.zeros(b, np.float32)}
    args = (jnp.asarray(mf)[idx[0].T][:, None], jnp.asarray(ln.T),
            jnp.ones(b, bool), jnp.asarray(seq_keys))
    return model, params, mf, batch, args
