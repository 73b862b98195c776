"""Decoder-hybrid-decoder language model as a sequence tower over pulled
token rows, its head tied to the table's rows.

≙ SambaY with differential attention ("Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation",
arXiv:2507.06607; Phi-4-mini-flash-reasoning): a self-decoder of Mamba
layers alternating with differential attention over a sliding window,
closed by one Mamba layer whose scan output is **the memory** and one
full-attention layer whose keys and values are **the shared KV**; then a
cross-decoder whose layers alternate Gated Memory Units (they gate the
memory, no scan of their own) and cross-attention (their own queries
over the shared KV).  As in ``looplm.py`` / ``hybridlm.py`` a token's
embedding is the ``mf`` row of its key, pulled per position and trained
by the sparse rule, and the model owns its next-token loss (``row_inputs``;
what the row models share is ``rowlm.py``).  Here the head is those rows
too (``head_keys``): the trainer pulls the rows of every held vocabulary
id once a step and hands them over as ``head`` [V, H]; the model keeps
no dense [H, V] parameter, and the gradient it returns to ``head`` goes
back through the parameter server's push (negated there, as the rows'
is: a tied head that climbed its own loss gradient diverged on the chip,
ARCHITECTURE.md, the head-keys contract).

Equations (one sequence of n tokens, x_i the row of token i; LN =
LayerNorm with gain and bias, an all-zero row passes no gradient; l the
layer's published index, which ``lambda_init`` reads):

    layer l:  h' = h + mixer_l(LN1(h));   h'' = h' + mlp(LN2(h'))
    mlp(b) = (silu(b Wg) * (b Wu)) Wd          out = LNf(h_L) E^T
    Mamba (d_inner x d_state, a = LN1(h)):
        [x ; z] = a W_in;   x <- silu(conv(x) + b_c)     causal, depthwise
        [dr ; B_t ; C_t] = x W_x;   dt = softplus(dr W_dt + b_dt)
        s_t = exp(dt_t * A) . s_{t-1} + (dt_t * x_t) (x) B_t,   s_0 = 0,
              A = -exp(A_log)  [d_inner x d_state]
        y_t = s_t C_t + D . x_t;   mixer = (y * silu(z)) W_out
        the last Mamba layer ahead of a GMU also hands on  m = y
    GMU:      mixer = (m * silu(a W_1)) W_2
    attention (window | full | cross), no position encoding:
        [q ; k ; v] = a Wqkv + b;  cross: q = a Wq + b, k and v those of
        the full-attention layer
        q -> [heads/2, 2, d] = (q1, q2);  k -> [kv/2, 2, d] = (k1, k2);
        v -> [kv/2, 2d];  differential head j reads kv group j // 2
        A_i = softmax(q_i k_i^T / sqrt(d) + mask),  mask: j <= t,
              j < length, and under a window also j > t - window
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
        lambda_init = 0.8 - 0.6 exp(-0.3 l)
        o = RMS_2d((A_1 - lambda A_2) v; g_sub) * (1 - lambda_init)
        mixer = concat(o) W_o + b_o
    loss = mean over target positions of -log softmax(out_i)[token_{i+1}]

**The selective scan runs in chunks** (``selective_scan``): one
``lax.scan`` over chunks of ``MAMBA_CHUNK`` tokens that carries s
[d_state, d_inner], a chunk's tokens unrolled inside it under a
checkpoint: the decay exp(dt A) and the drive are made token by token
from dt, x, B and never stored as [chunk, d_state, d_inner] arrays, so a
chunk is one elementwise chain over the state (no exponent is ever
positive).  An associative scan over chunks of 128 tokens, which writes
those arrays at every one of its levels, measured 3-4x slower on the chip
(PERF.md section 6).  d_inner is the minor dimension (d_state 16 would be
padded to a lane tile of 128).  The whole Mamba mixer runs in segments of
``MAMBA_SEGMENT`` tokens, one after another, each under a checkpoint and
handing the next the scan's state and the convolution's last inputs: a
sequence's projections ([n, 2 d_inner] and half a dozen [n, d_inner]) are
alive a segment at a time (3.4 GB of a backward's 10.5 at 8,192 tokens
otherwise).

**Window attention computes only the keys a query block can see**: a
block of ``SWA_QBLOCK`` queries slices its ``window + block`` keys out of
the sequence.  Full and cross attention run in blocks of ``ATTN_QBLOCK``
queries, ``ATTN_GROUPS`` groups of them one after another, a group's
keys cut at the group's end: the keys ahead of a group are skipped, the
ones ahead of a block inside its group are masked ((G + 1) / 2G of the
whole square: 53% at 16 groups, where the causal half is 50%).  A block
holds the queries of ``ATTN_KV_GROUPS`` kv groups (4 softmax maps each),
not of all ten: its [maps, block, keys] float32 scores then fit the
chip's fast memory, and the same blocks run 15-18x faster than with all
forty maps in one (PERF.md section 6).  The blocking (``causal_blocks``,
``sliding_blocks``) is handed what a block computes, so ``afmoe.py``'s
grouped-query heads run through the same code.

A layer passes on more than the residual stream (the memory, the shared
KV), so the half layers are a Python loop, a mixer one sequence at a time
and a feed-forward one token block at a time, each under a
``jax.checkpoint``; the memory and the KV are arguments of every reader's
checkpoint, and their gradients sum over the readers.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.models import rowlm
from paddlebox_tpu.models.hybridlm import causal_conv, swiglu
from paddlebox_tpu.models.rowlm import rms_norm
from paddlebox_tpu.utils import trace

_NEG = -1e30          # finite "minus infinity": a masked row stays finite
MAMBA_CHUNK = 8       # tokens a chunk of the selective scan (unrolled)
MAMBA_SEGMENT = 2048  # tokens a segment of a Mamba mixer
SWA_QBLOCK = 512      # queries a block of window attention
ATTN_QBLOCK = 256     # queries a block of full and cross attention
ATTN_GROUPS = 16      # groups of blocks whose keys end with the group
ATTN_KV_GROUPS = 1    # kv groups (4 softmax maps each) a block
MLP_BLOCK = 4096      # tokens a block of the feed-forward
HEAD_BLOCK = 1024     # tokens a block of the head (logits [block, vocabulary])
STATS = ("targets", "tokens_valid", "tokens_padded")
KINDS = ("mamba", "swa", "attn_full", "gmu", "attn_cross")


def layer_norm(z, g, b, eps):
    """LN(z; g, b).  An all-zero vector (a row the table has not created
    yet) passes no gradient, as ``rowlm.rms_norm``: its Jacobian there is
    g / sqrt(eps)."""
    dead = jnp.all(z == 0, axis=-1, keepdims=True)
    z = jnp.where(dead, jax.lax.stop_gradient(z), z)
    c = z - jnp.mean(z, axis=-1, keepdims=True)
    return g * c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True)
                                 + eps) + b


# -- the selective scan, in chunks -------------------------------------------

def selective_scan(x, dt, a, bm, cm, state=None):
    """y_t = s_t C_t of s_t = exp(dt_t * A) . s_{t-1} + (dt_t * x_t) (x)
    B_t from s_0 = ``state`` [N, D] (None: 0), for one run of tokens: x,
    dt [n, D], a [N, D] (negative), bm, cm [n, N]; returns (y [n, D],
    s_n).  n is padded to whole chunks with tokens that leave the state
    as it is (dt = 0)."""
    n, d = x.shape
    if state is None:
        state = jnp.zeros((a.shape[0], d), x.dtype)
    c = min(MAMBA_CHUNK, n)
    pad = -n % c
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, pad), (0, 0)))
                         for t in (x, dt, bm, cm))

    @jax.checkpoint     # the scan keeps a state a chunk, nothing else
    def one_chunk(state, xs):
        x_c, dt_c, b_c, c_c = xs                         # [C, D], [C, N]
        ys = []
        for t in range(c):      # unrolled: one elementwise chain a chunk
            state = jnp.exp(dt_c[t][None, :] * a) * state \
                + (dt_c[t] * x_c[t])[None, :] * b_c[t][:, None]
            ys.append(jnp.sum(state * c_c[t][:, None], axis=0))
        return state, jnp.stack(ys)

    state, y = jax.lax.scan(
        one_chunk, state,
        tuple(t.reshape((-1, c) + t.shape[1:]) for t in (x, dt, bm, cm)))
    return y.reshape(-1, d)[:n], state


# -- differential attention in query blocks ----------------------------------
# Head-major throughout ([..., tokens, d]): the device tiles an array's
# last two dimensions, and a [tokens, groups, 2, 64] array would be stored
# at eight times its size.

def _diff_block(qb, k, v, keep, lam, scale):
    """One block of queries against the keys it is given: qb [G, 2, 2, q,
    d] (kv group, head of the group, side), k [G, 2, k, d], v [G, k, 2d],
    keep [q, k] -> (A_1 - lam A_2) v, [G, 2, q, 2d]."""
    s = jnp.einsum("ghiqd,gikd->ghiqk", qb, k) * scale
    p = jax.nn.softmax(jnp.where(keep, s, _NEG), axis=-1)
    return jnp.einsum("ghqk,gke->ghqe", p[:, :, 0] - lam * p[:, :, 1], v)


def _query_blocks(q, blk: int, gb: int):
    """q [G, *inner, n, d] -> [n / blk, G / gb, gb, *inner, blk, d]:
    blocks of ``blk`` queries of ``gb`` kv groups (``inner``: the heads a
    group holds, and a differential head's two sides)."""
    g, n, d = q.shape[0], q.shape[-2], q.shape[-1]
    r = q.ndim - 3
    q = q.reshape((g // gb, gb) + q.shape[1:-2] + (n // blk, blk, d))
    return jnp.transpose(q, (2 + r,) + tuple(range(2 + r)) + (3 + r, 4 + r))


def _join_blocks(o):
    """[n / blk, G / gb, gb, *inner, blk, e] -> [G, *inner, n, e]."""
    nb, ng, gb = o.shape[:3]
    blk, e = o.shape[-2:]
    r = o.ndim - 5
    o = jnp.transpose(o, (1, 2) + tuple(range(3, 3 + r)) + (0, 3 + r, 4 + r))
    return o.reshape((ng * gb,) + o.shape[2:2 + r] + (nb * blk, e))


def _map_blocks(block, q, los, gb: int):
    """``block((qb, lo, first group))`` over every (query block, run of
    ``gb`` kv groups) of q [n / blk, G / gb, gb, *inner, blk, d], one
    after another."""
    nb, ng = q.shape[:2]
    out = jax.lax.map(block, (
        q.reshape((nb * ng,) + q.shape[2:]), jnp.repeat(los, ng),
        jnp.tile(jnp.arange(0, ng * gb, gb), nb)))
    return _join_blocks(out.reshape((nb, ng) + out.shape[1:]))


def _pad_tokens(t, before: int, after: int):
    """Pad the token axis (second to last) of t."""
    return jnp.pad(t, ((0, 0),) * (t.ndim - 2) + ((before, after), (0, 0)))


def causal_blocks(attend, q, k, v, length, *, qblock: int, groups: int,
                  gb: int):
    """Causal attention of one sequence over all its keys, in blocks:
    ``attend(qb, kb, vb, keep)`` for each block of ``qblock`` queries of
    ``gb`` kv groups (q [G, *inner, n, d]; k, v [G, ..., n, .], the token
    axis second to last; keep [block, keys]), one after another, each
    under a checkpoint.  The query blocks fall into ``groups`` groups, a
    group's keys cut where the group ends: the keys ahead of a group are
    skipped, the ones ahead of a block inside its group are masked.
    Returns [G, *inner_out, n, e]."""
    n = q.shape[-2]
    blk = min(qblock, n)
    pad = -n % blk
    if pad:
        q = _pad_tokens(q, 0, pad)
    nb = (n + pad) // blk
    per = -(-nb // min(groups, nb))                # blocks a group
    out = []
    for first in range(0, nb, per):
        last = min(first + per, nb)
        hi = min(last * blk, n)                    # keys this group meets
        kpos = jnp.arange(hi)
        k_g, v_g = k[..., :hi, :], v[..., :hi, :]

        @jax.checkpoint
        def block(args, k_g=k_g, v_g=v_g, kpos=kpos):
            qb, lo, g0 = args
            qpos = lo + jnp.arange(blk)
            keep = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < length)
            return attend(
                qb, jax.lax.dynamic_slice_in_dim(k_g, g0, gb, axis=0),
                jax.lax.dynamic_slice_in_dim(v_g, g0, gb, axis=0), keep)

        out.append(_map_blocks(
            block, _query_blocks(q[..., first * blk:last * blk, :], blk, gb),
            jnp.arange(first * blk, last * blk, blk), gb))
    return jnp.concatenate(out, axis=-2)[..., :n, :]


def sliding_blocks(attend, q, k, v, length, window: int, *, qblock: int,
                   gb: int):
    """The same over a sliding window: a query at t meets the keys
    t - window < j <= t.  A block of ``qblock`` queries that starts at
    ``lo`` slices keys lo - window .. lo + block - 1 out of the sequence
    and multiplies with nothing else."""
    n = q.shape[-2]
    blk = min(qblock, n)
    pad = -n % blk
    if pad:
        q = _pad_tokens(q, 0, pad)
    # keys in front of the sequence and behind it, so that every block's
    # slice lies inside
    k, v = _pad_tokens(k, window, pad), _pad_tokens(v, window, pad)

    def cut(t, g0, lo):          # kv groups g0.., keys lo - window..
        return jax.lax.dynamic_slice(
            t, (g0,) + (0,) * (t.ndim - 3) + (lo, 0),
            (gb,) + t.shape[1:-2] + (window + blk, t.shape[-1]))

    @jax.checkpoint
    def block(args):
        qb, lo, g0 = args
        kb, vb = cut(k, g0, lo), cut(v, g0, lo)
        qpos = lo + jnp.arange(blk)
        kpos = lo - window + jnp.arange(window + blk)
        keep = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window) \
            & (kpos[None, :] >= 0) & (kpos[None, :] < length)
        return attend(qb, kb, vb, keep)

    return _map_blocks(block, _query_blocks(q, blk, gb),
                       jnp.arange(0, n + pad, blk), gb)[..., :n, :]


def _kv_groups_a_block(g: int) -> int:
    return ATTN_KV_GROUPS if g % ATTN_KV_GROUPS == 0 else g


def diff_attention(q, k, v, length, lam):
    """Causal differential attention of one sequence over all its keys:
    q [G, 2, 2, n, d], k [G, 2, n, d], v [G, n, 2d], ``length`` valid
    tokens; returns [G, 2, n, 2d].  Blocks of ``ATTN_QBLOCK`` queries of
    ``ATTN_KV_GROUPS`` kv groups one after another, each under a
    checkpoint (a block's scores, [4 maps a group, block, keys] float32,
    then stay in the chip's fast memory); ``ATTN_GROUPS`` groups of query
    blocks, a group's keys cut where the group ends."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    return causal_blocks(
        lambda qb, kb, vb, keep: _diff_block(qb, kb, vb, keep, lam, scale),
        q, k, v, length, qblock=ATTN_QBLOCK, groups=ATTN_GROUPS,
        gb=_kv_groups_a_block(q.shape[0]))


def window_attention(q, k, v, length, lam, window: int):
    """The same over a sliding window (``sliding_blocks``, blocks of
    ``SWA_QBLOCK`` queries)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    return sliding_blocks(
        lambda qb, kb, vb, keep: _diff_block(qb, kb, vb, keep, lam, scale),
        q, k, v, length, window, qblock=SWA_QBLOCK,
        gb=_kv_groups_a_block(q.shape[0]))


def lambda_init(layer_id: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


class SambaYLM:
    row_inputs = True                 # takes unpooled rows, owns its loss
    extra_inputs = ("seq_keys",)
    seq_key_slot = 0                  # the sparse slot whose rows are the
                                      # sequence and fill the seq_keys plane

    def __init__(self, hidden: int, layers: Sequence[Tuple[int, str]],
                 vocab: int, *, heads: int, kv_heads: int, head_dim: int,
                 window: int, ffn: int, d_inner: int, d_state: int,
                 dt_rank: int, conv_kernel: int, eps: float = 1e-5,
                 init_std: float = 0.02, key_base: int = 1,
                 neg_seed: int = 0):
        """``layers``: (published index, kind) a layer, kind one of
        ``KINDS``.  The memory is the scan output of the last ``mamba``
        ahead of the first ``gmu``, the shared KV that of the last
        ``attn_full`` ahead of the first ``attn_cross``."""
        self.hidden, self.layers, self.vocab = hidden, tuple(layers), vocab
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.window, self.ffn = window, ffn
        self.d_inner, self.d_state = d_inner, d_state
        self.dt_rank, self.conv_kernel = dt_rank, conv_kernel
        self.eps, self.init_std = eps, init_std
        self.key_base, self.neg_seed = key_base, neg_seed
        kinds = [k for _, k in self.layers]
        unknown = set(kinds) - set(KINDS)
        if unknown or heads % 2 or kv_heads % 2 or heads % kv_heads:
            raise ValueError(f"layer kinds {sorted(unknown)} / heads "
                             f"{heads}, {kv_heads}: differential heads pair "
                             "up, and so do their keys")

        def giver(kind, reader):
            if reader not in kinds:
                return None
            ahead = [i for i, k in enumerate(kinds[:kinds.index(reader)])
                     if k == kind]
            if not ahead:
                raise ValueError(f"a {reader} layer needs a {kind} layer "
                                 "ahead of it")
            return ahead[-1]

        self.memory_from = giver("mamba", "gmu")
        self.kv_from = giver("attn_full", "attn_cross")

    def head_keys(self) -> np.ndarray:
        """The keys whose rows are the head, vocabulary id by id: the
        trainer keeps them in every pass's working set, pulls their rows
        once a step (``head`` of ``loss``) and pushes their gradient."""
        return np.arange(self.vocab, dtype=np.uint64) + np.uint64(
            self.key_base)

    # -- parameters ---------------------------------------------------------
    def init(self, key):
        """The parameter tree, made by one program."""
        return jax.jit(self._init)(key)

    def _init(self, key):
        h, std = self.hidden, self.init_std
        keys = iter(jax.random.split(key, 16 * len(self.layers) + 2))

        def w(*shape, std=std):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        def ones(*shape):   # one buffer each: the step donates every leaf
            return jnp.ones(shape, jnp.float32)

        def zeros(*shape):
            return jnp.zeros(shape, jnp.float32)

        def mamba():
            di, ns, r, ck = (self.d_inner, self.d_state, self.dt_rank,
                             self.conv_kernel)
            lim = 1.0 / math.sqrt(ck)   # PyTorch's Conv1d default
            # the step dt log-uniform in [1e-3, 1e-1], b_dt its inverse
            # softplus; A = -(1 .. d_state) on every channel
            dt = jnp.exp(jax.random.uniform(
                next(keys), (di,), jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            return {"w_in": w(h, 2 * di),
                    "conv": jax.random.uniform(next(keys), (ck, di),
                                               jnp.float32, -lim, lim),
                    "b_conv": zeros(di), "w_x": w(di, r + 2 * ns),
                    "w_dt": w(r, di), "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                    "a_log": jnp.log(jnp.broadcast_to(
                        jnp.arange(1, ns + 1, dtype=jnp.float32),
                        (di, ns))) + zeros(di, ns),
                    "d": ones(di), "w_out": w(di, h)}

        def attention(cross: bool):
            a, kv, d = (self.heads * self.head_dim,
                        self.kv_heads * self.head_dim, self.head_dim)
            wide = a if cross else a + 2 * kv
            return {"wqkv": w(h, wide), "bqkv": zeros(wide),
                    "lq1": w(d, std=0.1), "lk1": w(d, std=0.1),
                    "lq2": w(d, std=0.1), "lk2": w(d, std=0.1),
                    "g_sub": ones(2 * d), "wo": w(a, h), "bo": zeros(h)}

        def gmu():
            return {"w1": w(h, self.d_inner), "w2": w(self.d_inner, h)}

        def mixer(kind):
            if kind == "mamba":
                return mamba()
            return gmu() if kind == "gmu" else attention(kind == "attn_cross")

        return {
            "layers": [{"ln1_g": ones(h), "ln1_b": zeros(h),
                        "ln2_g": ones(h), "ln2_b": zeros(h),
                        "mixer": mixer(kind),
                        "mlp": {"wg": w(h, self.ffn), "wu": w(h, self.ffn),
                                "wd": w(self.ffn, h)}}
                       for _, kind in self.layers],
            "lnf_g": ones(h), "lnf_b": zeros(h),
        }

    # -- the mixers, one sequence ---------------------------------------------
    def mamba(self, w, h):
        """h + Mamba's mixer on LN1(h) for h [n, H] -> (h', y): y [n,
        d_inner] is the scan's output before the gate, the memory.
        Segments of ``MAMBA_SEGMENT`` tokens one after another, each
        under a checkpoint; a segment hands on the scan's state and the
        convolution's last kernel - 1 inputs."""
        n, hd = h.shape
        di, ns, r = self.d_inner, self.d_state, self.dt_rank
        mx, ck = w["mixer"], self.conv_kernel
        seg = min(MAMBA_SEGMENT, n)
        pad = -n % seg      # tokens past the end: nothing reads them
        if pad:
            h = jnp.pad(h, ((0, pad), (0, 0)))

        @jax.checkpoint
        def segment(carry, hs):
            tail, state = carry
            a = layer_norm(hs, w["ln1_g"], w["ln1_b"], self.eps)
            with trace.device_scope("tower.mamba"):
                xz = a @ mx["w_in"]
                pre = jnp.concatenate([tail, xz[:, :di]])
                x = jax.nn.silu(causal_conv(pre[None], mx["conv"])[0, ck - 1:]
                                + mx["b_conv"])
                proj = x @ mx["w_x"]
                dt = jax.nn.softplus(proj[:, :r] @ mx["w_dt"] + mx["b_dt"])
                y, state = selective_scan(
                    x, dt, -jnp.exp(mx["a_log"]).T, proj[:, r:r + ns],
                    proj[:, r + ns:], state)
                y = y + mx["d"] * x
                out = (y * jax.nn.silu(xz[:, di:])) @ mx["w_out"]
            return (pre[-(ck - 1):], state), (hs + out, y)

        _, (out, y) = jax.lax.scan(
            segment, (jnp.zeros((ck - 1, di), h.dtype),
                      jnp.zeros((ns, di), h.dtype)),
            h.reshape(-1, seg, hd))
        return out.reshape(-1, hd)[:n], y.reshape(-1, di)[:n]

    def attention(self, kind, layer_id, w, a, length, kv):
        """A differential-attention mixer on a [n, H] -> (mixer, (k, v));
        ``kv``: the shared keys and values a cross layer reads, as the
        full layer projected them, [n, kv heads x d] each."""
        n = a.shape[0]
        d, g = self.head_dim, self.kv_heads // 2
        qkv = a @ w["wqkv"] + w["bqkv"]
        wide, kv_wide = self.heads * d, self.kv_heads * d
        if kind == "attn_cross":
            k, v = kv
        else:
            k, v = qkv[:, wide:wide + kv_wide], qkv[:, wide + kv_wide:]
        # head-major: q [G, 2, 2, n, d] (head of the group, side), k [G,
        # 2, n, d], v [G, n, 2d]
        q = jnp.transpose(qkv[:, :wide].reshape(
            n, g, self.heads // self.kv_heads, 2, d), (1, 2, 3, 0, 4))
        k_h = jnp.transpose(k.reshape(n, g, 2, d), (1, 2, 0, 3))
        v_h = jnp.transpose(v.reshape(n, g, 2 * d), (1, 0, 2))
        init = lambda_init(layer_id)
        lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
            - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + init
        if kind == "swa":
            o = window_attention(q, k_h, v_h, length, lam, self.window)
        else:
            o = diff_attention(q, k_h, v_h, length, lam)
        o = rms_norm(o, w["g_sub"], self.eps) * (1.0 - init)
        o = jnp.transpose(o, (2, 0, 1, 3)).reshape(n, wide)
        return o @ w["wo"] + w["bo"], (k, v)

    def mix(self, i, w, h, lengths, memory, kv):
        """Layer i's h + mixer(LN1(h)) on h [B, n, H], one sequence after
        another, each under its own checkpoint (a Mamba mixer: each of
        its segments); returns (h, the memory or the shared KV this layer
        hands on, else None)."""
        layer_id, kind = self.layers[i]
        gives = (kind == "mamba" and i == self.memory_from) \
            or (kind == "attn_full" and i == self.kv_from)
        shared = {"gmu": memory, "attn_cross": kv}.get(kind)

        if kind == "mamba":     # its segments carry the checkpoints
            def one(args):
                out, y = self.mamba(w, args[0])
                return out, (y if gives else None)
        else:
            @jax.checkpoint
            def one(args):
                h, length, shared = args
                a = layer_norm(h, w["ln1_g"], w["ln1_b"], self.eps)
                with trace.device_scope("tower." + kind):
                    if kind == "gmu":
                        out, handed = (shared * jax.nn.silu(
                            a @ w["mixer"]["w1"])) @ w["mixer"]["w2"], None
                    else:
                        out, handed = self.attention(
                            kind, layer_id, w["mixer"], a, length, shared)
                return h + out, (handed if gives else None)

        return jax.lax.map(one, (h, lengths, shared))

    def feed_forward(self, w, h):
        """h + mlp(LN2(h)) on h [B, n, H], ``MLP_BLOCK`` tokens at a
        time, each block under a checkpoint."""
        shape = h.shape
        flat = h.reshape(-1, shape[-1])
        blk = min(MLP_BLOCK, flat.shape[0])
        pad = -flat.shape[0] % blk
        if pad:
            flat = jnp.pad(flat, ((0, pad), (0, 0)))

        @jax.checkpoint
        def block(hb):
            x = layer_norm(hb, w["ln2_g"], w["ln2_b"], self.eps)
            with trace.device_scope("tower.mlp"):
                return hb + swiglu(x, w["mlp"]["wg"], w["mlp"]["wu"],
                                   w["mlp"]["wd"])

        out = jax.lax.map(block, flat.reshape(-1, blk, shape[-1]))
        return out.reshape(-1, shape[-1])[:flat.shape[0] - pad].reshape(shape)

    def head_terms(self, head, h, targets, negatives):
        """h [M, H], head [V, H] -> cross-entropy [M], log p of target
        and of negative [M]; token blocks under a checkpoint."""
        @jax.checkpoint
        def block(args):
            hb, yb, nb = args
            lse, zy, zn = rowlm.head_logits(head.T, hb, yb, nb)
            return lse - zy, zy - lse, zn - lse

        return rowlm.map_token_blocks(block, HEAD_BLOCK, h, targets,
                                      negatives)

    def loss(self, params, rows, lengths, valid, seq_keys, head):
        """``head`` [V, H]: the pulled rows of ``head_keys()``."""
        x = rows[:, self.seq_key_slot]                        # [B, n, H]
        ln = jnp.where(valid, lengths[:, self.seq_key_slot], 0)
        b, n, hd = x.shape
        targets, has_target, negatives = rowlm.next_token_plan(
            seq_keys, ln, valid, n, self.key_base, self.vocab, self.neg_seed)
        h, memory, kv = x, None, None
        for i, w in enumerate(params["layers"]):
            h, handed = self.mix(i, w, h, ln, memory, kv)
            if handed is not None and self.layers[i][1] == "mamba":
                memory = handed
            elif handed is not None:
                kv = handed
            h = self.feed_forward(w, h)
        h = layer_norm(h, params["lnf_g"], params["lnf_b"], self.eps)
        ce, lp_pos, lp_neg = self.head_terms(
            head, h.reshape(b * n, hd), targets.reshape(-1),
            negatives.reshape(-1))
        with trace.device_scope("tower.head_loss"):
            wt = has_target.astype(jnp.float32)
            count = jnp.sum(wt)
            loss = jnp.sum(ce * wt) / jnp.maximum(count, 1.0)
            tokens = rowlm.token_counts(ln, valid, n)
            aux = {**rowlm.auc_pairs(lp_pos, lp_neg, has_target, self.vocab),
                   "stats": jnp.stack([count, tokens, b * n - tokens])}
        return loss, jax.lax.stop_gradient(aux)

    def record_stats(self, total, steps: int) -> None:
        """Counters of a pass: ``total`` is ``stats`` summed over its
        ``steps`` steps (the trainer reads it back once a pass)."""
        _, valid, padded = (float(v) for v in total[:len(STATS)])
        rowlm.record_padding(valid, padded)
