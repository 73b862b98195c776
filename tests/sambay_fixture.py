"""A tiny decoder-hybrid-decoder model (``models/sambay.py``) and the
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
# block sizes at which the tests' two dozen positions take several of
# each (the module's constants are sized for 8,192)
BLOCKS = (("MAMBA_CHUNK", 4), ("MAMBA_SEGMENT", 8), ("SWA_QBLOCK", 4),
          ("ATTN_QBLOCK", 4), ("ATTN_GROUPS", 3), ("ATTN_KV_GROUPS", 1),
          ("MLP_BLOCK", 16),
          ("HEAD_BLOCK", 8))


def config(vocab=VOCAB, vocab_first=0, layers=6, layer_first=14):
    """Published layers 14-19 of 32 (Mamba, window, Mamba that gives the
    memory, full attention that gives the KV, GMU, cross): hidden 64, 4 /
    2 heads of 16, d_inner 128, d_state 4, window 8, ``vocab`` ids held
    from ``vocab_first`` on."""
    return {
        "hidden_size": HIDDEN, "num_hidden_layers": layers,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 8, "mb_per_layer": 2,
        "layer_norm_eps": 1e-5, "vocab_size": vocab,
        "mamba": {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 4},
        "published": {"num_hidden_layers": 32, "vocab_size": 8 * VOCAB},
        "share": {"chips": 8, "layer_first": layer_first,
                  "vocab_first": vocab_first},
        "loss": {"init_std": 0.02, "negative_seed": 11,
                 "key_base": 1 + vocab_first},
        "table": {"embedx_dim": HIDDEN, "sgd": dict(SGD)}}


def module(kind):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_phi4_mini_flash",
        os.path.join(ROOT, "benchmark", kind, "phi4_mini_flash.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_of(cfg):
    return module("models").build(cfg)


def seeded(cfg, seed=0, lengths=(CAP, 13, 1)):
    """Model, parameters (norm gains and biases, the attention biases and
    the lambdas moved off their starts), a table whose row r is key r
    (every head key held, as the trainer keeps them; all created), and
    one batch with padded tails and a length-1 sequence, as the model and
    as the reference read it."""
    model = model_of(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

    def moved(a, scale=0.1):
        return a + scale * jax.random.normal(next(noise), a.shape)

    for w in params["layers"]:
        for g in ("ln1_g", "ln1_b", "ln2_g", "ln2_b"):
            w[g] = moved(w[g])
        for g in ("bqkv", "bo", "g_sub", "b_conv", "d"):
            if g in w["mixer"]:
                w["mixer"][g] = moved(w["mixer"][g])
    params["lnf_g"], params["lnf_b"] = (moved(params[g])
                                        for g in ("lnf_g", "lnf_b"))
    rng = np.random.default_rng(seed)
    vocab = cfg["vocab_size"]
    rows = vocab + 1
    b = len(lengths)
    ln = np.array([lengths], np.int32)
    mask = np.arange(CAP)[None, :, None] < ln[:, None, :]
    idx = np.where(mask, rng.integers(1, rows, (1, CAP, b)), 0
                   ).astype(np.int32)
    mf = rng.normal(0, 0.05, (rows, HIDDEN)).astype(np.float32)
    mf[0] = 0
    base = cfg["loss"]["key_base"]
    seq_keys = np.where(mask[0].T, idx[0].T - 1 + base, 0).astype(np.int32)
    head_rows = np.arange(1, rows, dtype=np.int32)
    batch = {"indices": idx, "lengths": ln, "valid": np.ones(b, bool),
             "seq_keys": seq_keys, "labels": np.zeros(b, np.float32),
             "head_rows": head_rows}
    args = (jnp.asarray(mf)[idx[0].T][:, None], jnp.asarray(ln.T),
            jnp.ones(b, bool), jnp.asarray(seq_keys),
            jnp.asarray(mf)[head_rows])
    return model, params, mf, batch, args
