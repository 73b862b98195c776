"""Hybrid linear-attention language model as a sequence tower over pulled
token rows.

≙ Kimi Linear ("Kimi Linear: An Expressive, Efficient Attention
Architecture", arXiv:2510.26692): layers whose mixer is Kimi Delta
Attention (KDA: a delta-rule linear attention with a per-channel decay)
or, one in four, latent attention without rotary position (MLA, NoPE);
whose feed-forward is a dense SwiGLU (the leading layer) or sigmoid-routed
experts, top-k of all of them plus one shared.  As in ``looplm.py`` the
token embedding is the ``mf`` row of the token's key, pulled per position
and trained by the sparse rule, and the model owns its next-token loss
(``row_inputs``; what the two share is ``rowlm.py``).

The layers differ, so the tower is a Python loop over them, each half of
a layer (mixer, feed-forward) under a ``jax.checkpoint`` (``RoutedLM``:
the loop, the feed-forwards, head, loss and counters, which
``afmoe.AfmoeLM`` shares).  Equations (one
sequence of n tokens, x_i the row of token i, RMS as ``rowlm.rms_norm``):

    layer l:  h' = h + mixer_l(RMS(h; g1));  h'' = h' + ffn_l(RMS(h'; g2))
    out = RMS(h_L; gf) W_head;  loss = mean over target positions of
          -log softmax(out_i)[token_{i+1}]

    KDA (heads x d, a = RMS(h; g1)):
        q, k, v = silu(conv(a Wq)), silu(conv(a Wk)), silu(conv(a Wv))
                  conv(z)_t = sum_j c_j * z_{t-j}, j < kernel: causal,
                  depthwise
        q, k <- q / |q| * d^-1/2, k / |k|          (|z| = sqrt(z.z + 1e-6))
        g_t = -exp(A_log) * softplus((a Wf1) Wf2 + dt_bias)  [heads x d]
        alpha_t = exp(g_t);  beta_t = sigmoid(a Wb)           one a head
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
              + beta_t k_t v_t^T,   S_0 = 0              [d x d] a head
        o_t = S_t^T q_t
        mixer = [RMS_head(o_t; g_o) * sigmoid((a Wg1) Wg2)] Wo
    MLA (heads, no rotation on any dimension):
        q_t = a Wq  [heads x (nope + rope)];  [c_t ; kr_t] = a Wkva
        [kc_t,h ; v_t,h] = RMS(c_t; g_c) Wkvb;   k_t,h = [kc_t,h ; kr_t]
        o = softmax(q k^T / sqrt(nope + rope) + causal, j < length) v
        mixer = o Wo
    dense ffn: (silu(b Wg) * (b Wu)) Wd,  b = RMS(h'; g2)
    routed ffn: s = sigmoid(b Wr) over ALL experts;
        chosen = top-k of s + bias;  w_e = scale * s_e / sum_chosen s
        ffn = sum over e chosen AND held here of w_e E_e(b) + E_shared(b)

**KDA runs chunk-parallel** (``kda_chunked``).  With u_t = beta_t (v_t -
S_{t-1}^T (alpha_t * k_t)) the recurrence is S_t = Diag(alpha_t) S_{t-1}
+ k_t u_t^T.  Inside a chunk of C tokens that starts from S_0, with G_r
the cumulative log decay,

    (I + Diag(beta) A) U = Diag(beta) (V - (K * e^G) S_0),
        A_ri = sum_d k_rd k_id e^(G_rd - G_id), i < r   (forward
        substitution: the WY form of the delta rule)
    O = (Q * e^G) S_0 + P U,   P_ri = sum_d q_rd k_id e^(G_rd - G_id), i <= r
    S_C = Diag(e^G_C) S_0 + (K * e^(G_C - G))^T U

so everything that does not hold S_0 (A, P, the inverse, its products
with K and V) is computed for all chunks at once, in blocks of chunks
under a checkpoint, and one ``lax.scan`` over chunks carries S.  No
exponent is ever positive: A and P are built in sub-chunks of
``KDA_SUB`` tokens, pairs of one sub-chunk by their own difference
G_r - G_i, a pair from two sub-chunks through the decay at the later
one's start.  The state algebra (every product with e^G in it, the
inverse, the scan) is float32 at ``Precision.HIGHEST``; the projections
multiply at the device's default, as every tower here does.

**The routed experts** (``parallel/moe.py::routed_experts``) are told
which experts this chip holds: the router scores all of them, the
assignments of held experts are sorted by expert, cut into blocks that
hold one expert each and multiplied block by block, as many blocks as
the data fill (none is dropped), nothing is computed for an expert that
lies elsewhere and nothing stands in for it.  ``stats`` carries what the
layer counted (assignments held, dropped, tokens each held expert
received).
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddlebox_tpu.models import rowlm
from paddlebox_tpu.models.rowlm import rms_norm
from paddlebox_tpu.parallel import moe
from paddlebox_tpu.utils import trace
from paddlebox_tpu.utils.monitor import stat_add, stat_set

_NEG = -1e30          # finite "minus infinity": a masked row stays finite
_HI = jax.lax.Precision.HIGHEST
KDA_CHUNK = 64        # tokens a chunk of the delta rule (a power of two)
KDA_SUB = 16          # tokens a sub-chunk of A and P
KDA_BLOCK = 8         # chunks a block of the chunk-local algebra
KDA_SEQS = 2          # sequences a group of a KDA mixer
MLA_QBLOCK = 128      # queries a block of latent attention
HEAD_BLOCK = 1024     # tokens a block of the head (logits [block, vocabulary])
ROUTED_OUT = "tower.moe.held_out"   # the name ``keep_routed`` saves by
STATS = ("targets", "tokens_valid", "tokens_padded",
         "moe_assignments_held", "moe_dropped_assignments")
#   ... then tokens received, one a (routed layer, held expert); under a
#   balancing bias then positions that chose, one a (routed layer, expert)


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def causal_conv(z, c):
    """conv(z)_t = sum_j c[j] * z_{t-j} on z [B, n, D], c [kernel, D]."""
    n = z.shape[1]
    zp = jnp.pad(z, ((0, 0), (c.shape[0] - 1, 0), (0, 0)))
    return sum(c[j] * zp[:, c.shape[0] - 1 - j: c.shape[0] - 1 - j + n]
               for j in range(c.shape[0]))


def l2_normalise(z):
    return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)


# -- the delta rule, chunk-parallel ------------------------------------------

def _unit_lower_inverse_fwd(low):
    """(I + low)^-1 for strictly lower ``low`` [..., C, C], C a power of
    two: the inverse of [[A, 0], [X, B]] is [[A^-1, 0], [-B^-1 X A^-1,
    B^-1]], from 1 x 1 blocks up."""
    c = low.shape[-1]
    lead = low.shape[:-2]
    inv = jnp.ones(lead + (c, 1, 1), low.dtype)
    s = 1
    while s < c:
        nb = c // (2 * s)
        blocks = low.reshape(lead + (nb, 2, s, nb, 2, s))
        x = jnp.moveaxis(jnp.diagonal(blocks[..., :, 1, :, :, 0, :],
                                      axis1=-4, axis2=-2), -1, -3)
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        x = -jnp.einsum("...ij,...jk,...kl->...il", b, x, a, precision=_HI)
        inv = jnp.concatenate(
            [jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
             jnp.concatenate([x, b], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


@jax.custom_vjp
def unit_lower_inverse(low):
    return _unit_lower_inverse_fwd(low)


def _uli_fwd(low):
    m = _unit_lower_inverse_fwd(low)
    return m, m


def _uli_bwd(m, g):
    # M = (I + L)^-1:  dM = -M dL M
    c = m.shape[-1]
    d = -jnp.einsum("...ji,...jk,...lk->...il", m, g, m, precision=_HI)
    return (jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), d, 0.0),)


unit_lower_inverse.defvjp(_uli_fwd, _uli_bwd)


def _chunk_local(q, k, v, g, beta):
    """One chunk's part that does not hold the incoming state: q, k, g
    [C, heads, d], v [C, heads, dv], beta [C, heads] -> W = T (K * e^G)
    [heads, C, d], U0 = T V [heads, C, dv], P [heads, C, C], with T =
    (I + Diag(beta) A)^-1 Diag(beta)."""
    c, sub = q.shape[0], min(KDA_SUB, q.shape[0])
    ns = c // sub
    q, k, v, g = (jnp.moveaxis(t, 1, 0) for t in (q, k, v, g))  # [h, C, .]
    beta = beta.T[..., None]                                     # [h, C, 1]
    cum = jnp.cumsum(g, axis=1)
    rows_a, rows_p = [], []
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    for a in range(ns):
        lo = a * sub
        ca, ka, qa = (t[:, lo:lo + sub] for t in (cum, k, q))
        # pairs of this sub-chunk, by their own difference (<= 0)
        diff = jnp.where(tri[None, :, :, None],
                         ca[:, :, None, :] - ca[:, None, :, :], 0.0)
        decay = jnp.exp(diff) * tri[None, :, :, None]
        kd = ka[:, None, :, :] * decay                    # k_i e^(G_r - G_i)
        blocks_a = [jnp.sum(ka[:, :, None, :] * kd, axis=-1)]
        blocks_p = [jnp.sum(qa[:, :, None, :] * kd, axis=-1)]
        if a:
            # pairs with an earlier sub-chunk, through the decay at this
            # one's start: both exponents <= 0
            start = cum[:, lo - 1][:, None, :]
            left = jnp.exp(ca - start)
            right = k[:, :lo] * jnp.exp(start - cum[:, :lo])
            blocks_a.insert(0, jnp.einsum("hrd,hid->hri", ka * left, right,
                                          precision=_HI))
            blocks_p.insert(0, jnp.einsum("hrd,hid->hri", qa * left, right,
                                          precision=_HI))
        pad = ((0, 0), (0, 0), (0, c - lo - sub))
        rows_a.append(jnp.pad(jnp.concatenate(blocks_a, axis=-1), pad))
        rows_p.append(jnp.pad(jnp.concatenate(blocks_p, axis=-1), pad))
    big_a = jnp.concatenate(rows_a, axis=1)               # [h, C, C]
    big_p = jnp.concatenate(rows_p, axis=1)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    inv = unit_lower_inverse(jnp.where(strict, beta * big_a, 0.0))
    w = jnp.einsum("hri,hid->hrd", inv, beta * k * jnp.exp(cum),
                   precision=_HI)
    u0 = jnp.einsum("hri,hid->hrd", inv, beta * v, precision=_HI)
    return w, u0, big_p


def kda_chunked(q, k, v, g, beta):
    """o_t = S_t^T q_t of the gated delta rule (module docstring) for q,
    k, g [B, n, heads, d], v [B, n, heads, dv], beta [B, n, heads];
    returns o [B, n, heads, dv].  n is padded to whole chunks with
    tokens that write nothing (k = v = 0)."""
    b, n, nh, d = q.shape
    dv = v.shape[-1]
    c = min(KDA_CHUNK, 1 << max(n - 1, 0).bit_length())
    pad = -n % c
    if pad:
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (n + pad) // c

    def chunks(t):                       # [B, n, ...] -> [B * nc, C, ...]
        return t.reshape((b * nc, c) + t.shape[2:])

    w, u0, p = jax.lax.map(
        jax.checkpoint(lambda args: _chunk_local(*args)),
        tuple(chunks(t) for t in (q, k, v, g, beta)),
        batch_size=min(KDA_BLOCK, b * nc))

    def by_chunk(t):             # [B * nc, ...] -> [nc, B, ...]
        return jnp.moveaxis(t.reshape((b, nc) + t.shape[1:]), 1, 0)

    def head_major(t):           # [B, n, h, .] -> [nc, B, h, C, .]
        return jnp.moveaxis(
            jnp.moveaxis(t.reshape((b, nc, c) + t.shape[2:]), 1, 0), 2, 3)

    @jax.checkpoint     # the scan keeps a state a chunk, nothing else
    def one_chunk(state, xs):
        w_c, u0_c, p_c, q_c, k_c, g_c = xs
        cum = jnp.cumsum(g_c, axis=2)                      # [B, h, C, d]
        last = cum[:, :, -1:, :]
        u = u0_c - jnp.einsum("bhcd,bhdv->bhcv", w_c, state, precision=_HI)
        o = jnp.einsum("bhcd,bhdv->bhcv", q_c * jnp.exp(cum), state,
                       precision=_HI) \
            + jnp.einsum("bhri,bhiv->bhrv", p_c, u, precision=_HI)
        state = jnp.exp(last[:, :, 0, :, None]) * state + jnp.einsum(
            "bhcd,bhcv->bhdv", k_c * jnp.exp(last - cum), u, precision=_HI)
        return state, o

    _, o = jax.lax.scan(
        one_chunk, jnp.zeros((b, nh, d, dv), q.dtype),
        (by_chunk(w), by_chunk(u0), by_chunk(p), head_major(q),
         head_major(k), head_major(g)))
    # [nc, B, h, C, dv] -> [B, n, h, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(b, nc * c, nh, dv)
    return o[:, :n]


# -- latent attention in query blocks ----------------------------------------

def mla_attention(q_nope, q_rope, k_nope, k_rope, v, lengths):
    """softmax(q k^T / sqrt(nope + rope) + causal, j < length) v with k =
    [k_nope ; k_rope], k_rope [B, n, rope] shared by the heads; the
    others [B, n, heads, .].  Blocks of ``MLA_QBLOCK`` queries one after
    another (``lax.map``), each under a checkpoint: the [heads, block, n]
    scores of one block are alive at a time, none is kept.  A block
    meets every key and masks the ones ahead of it: a Python loop over
    blocks with keys cut at each block's end does half the products, and
    the compiler then runs all the blocks' recomputations side by side
    (15.5 GB of temporaries at 4 x 4,096 tokens)."""
    b, n, nh, _ = q_nope.shape
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
    blk = min(MLA_QBLOCK, n)
    pad = -n % blk
    if pad:
        q_nope, q_rope = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for t in (q_nope, q_rope))
    kpos = jnp.arange(n)

    @jax.checkpoint
    def block(args):
        qn, qr, lo = args                             # [B, blk, heads, .]
        s = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
             + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope)) * scale
        qpos = lo + jnp.arange(blk)
        keep = (kpos[None, None, :] <= qpos[None, :, None]) \
            & (kpos[None, None, :] < lengths[:, None, None])
        p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def blocks(t):               # [B, n, ...] -> [n / blk, B, blk, ...]
        return jnp.moveaxis(t.reshape((b, -1, blk) + t.shape[2:]), 1, 0)

    out = jax.lax.map(block, (blocks(q_nope), blocks(q_rope),
                              jnp.arange(0, n + pad, blk)))
    return jnp.moveaxis(out, 0, 1).reshape((b, n + pad) + out.shape[3:])[:, :n]


class RoutedLM:
    """What a tower of layers that differ shares (``HybridLM`` here,
    ``afmoe.AfmoeLM``): the layer loop, each half layer (mixer,
    feed-forward) under a ``jax.checkpoint``; the dense and routed
    feed-forwards; the head in token blocks, the loss and the counters.
    A subclass brings its mixers: ``init_mixer(kind, w, ones, keys)``
    their leaves, ``mixer(kind, w, a, lengths)`` their output on a [B,
    n, H] and ``seqs_a_group(kind)`` how many sequences one checkpoint
    of it takes (None: the whole batch).

    Options a subclass may set: ``sandwich`` (a norm after each sublayer
    as well as before it: h' = h + RMS(mixer(RMS(h; g1)); g1_post), the
    same for the feed-forward), ``input_scale`` (the rows times a
    constant before the first layer), ``balance_rate`` (the routing
    bias is moved after every update by the counts the step routed:
    ``after_update``; 0 holds it where it is) and ``keep_routed`` (the
    held experts' part of a routed layer is kept from the forward for the
    backward, by name past the feed-forward's checkpoint, instead of
    being computed again: the expert blocks run once fewer a step, for
    [B * n, H] float32 more memory a routed layer)."""
    row_inputs = True                 # takes unpooled rows, owns its loss
    extra_inputs = ("seq_keys",)
    seq_key_slot = 0                  # the sparse slot whose rows are the
                                      # sequence and fill the seq_keys plane
    after_update = None               # (params, aux) -> params after Adam

    def __init__(self, hidden: int, layers: Sequence[Tuple[str, str]],
                 vocab: int, *, ffn: int, experts: int,
                 experts_held: Sequence[int], top_k: int, expert_ffn: int,
                 shared_experts: int, routed_scale: float, eps: float = 1e-5,
                 init_std: float = 0.02, key_base: int = 1,
                 neg_seed: int = 0, sandwich: bool = False,
                 input_scale: float = 1.0, balance_rate: float = 0.0,
                 keep_routed: bool = False):
        """``layers``: a (mixer, ffn) pair a layer, ffn ``dense`` |
        ``moe``.  ``experts`` is the router's width, ``experts_held`` the
        ids of the experts this chip holds."""
        self.hidden, self.layers, self.vocab = hidden, tuple(layers), vocab
        self.ffn, self.experts = ffn, experts
        self.experts_held = tuple(int(e) for e in experts_held)
        self.top_k, self.expert_ffn = top_k, expert_ffn
        self.shared_experts, self.routed_scale = shared_experts, routed_scale
        self.eps, self.init_std = eps, init_std
        self.key_base, self.neg_seed = key_base, neg_seed
        self.sandwich, self.input_scale = sandwich, input_scale
        self.balance_rate = balance_rate
        self.keep_routed = keep_routed
        self.moe_layers = sum(f == "moe" for _, f in self.layers)
        if balance_rate and self.moe_layers:
            self.after_update = self.balance_bias

    # -- parameters ---------------------------------------------------------
    def init(self, key):
        """The parameter tree, made by one program (a few dozen leaves
        of a dozen shapes: leaf by leaf each draw compiles alone)."""
        return jax.jit(self._init)(key)

    def _init(self, key):
        h, std = self.hidden, self.init_std
        keys = iter(jax.random.split(key, 32 * len(self.layers) + 2))

        def w(*shape):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        def ones(*shape):   # one buffer each: the step donates every leaf
            return jnp.ones(shape, jnp.float32)

        def dense():
            return {"wg": w(h, self.ffn), "wu": w(h, self.ffn),
                    "wd": w(self.ffn, h)}

        def routed():
            e, f = len(self.experts_held), self.expert_ffn
            fs = f * self.shared_experts
            return {"router": w(h, self.experts),
                    "router_bias": jnp.zeros((self.experts,), jnp.float32),
                    "wg": w(e, h, f), "wu": w(e, h, f), "wd": w(e, f, h),
                    "sg": w(h, fs), "su": w(h, fs), "sd": w(fs, h)}

        def layer(mixer, ffn):
            out = {"g1": ones(h), "g2": ones(h)}
            if self.sandwich:
                out["g1_post"], out["g2_post"] = ones(h), ones(h)
            out["mixer"] = self.init_mixer(mixer, w, ones, keys)
            out["ffn"] = dense() if ffn == "dense" else routed()
            return out

        return {
            "layers": [layer(mixer, ffn) for mixer, ffn in self.layers],
            "gf": ones(h),
            "head": w(h, self.vocab),
        }

    # -- the layers ---------------------------------------------------------
    def routed(self, w, x, live):
        """The routed feed-forward on x [B, n, H]; ``live`` [B, n] marks
        the positions that are routed (inside their sequence).  Returns
        the layer's output and its counts (``moe.routed_experts``)."""
        b, n, h = x.shape
        flat = x.reshape(b * n, h)
        with trace.device_scope("tower.moe"):
            out, counts = moe.routed_experts(
                flat, live.reshape(-1), w["router"],
                jax.lax.stop_gradient(w["router_bias"]),
                (w["wg"], w["wu"], w["wd"]), self.experts_held, self.top_k,
                self.routed_scale)
            out = checkpoint_name(out, ROUTED_OUT)
            with trace.device_scope("shared_expert"):
                out = out + swiglu(flat, w["sg"], w["su"], w["sd"])
        return out.reshape(b, n, h), counts

    def mix(self, mixer, w, h, lengths):
        """h + mixer(RMS(h; g1)) on h [B, n, H], ``seqs_a_group(mixer)``
        sequences at a time, one group after another, each under its own
        checkpoint."""
        def one(h, lengths):
            a = rms_norm(h, w["g1"], self.eps)
            with trace.device_scope("tower." + mixer):
                out = self.mixer(mixer, w["mixer"], a, lengths)
                if self.sandwich:
                    out = rms_norm(out, w["g1_post"], self.eps)
                return h + out

        b, g = h.shape[0], self.seqs_a_group(mixer)
        if not g or b <= g or b % g:
            return jax.checkpoint(one)(h, lengths)
        out = jax.lax.map(
            lambda args: jax.checkpoint(one)(*args),
            (h.reshape((b // g, g) + h.shape[1:]),
             lengths.reshape(b // g, g)))
        return out.reshape(h.shape)

    def feed_forward(self, ffn, w, h, lengths):
        """h + ffn(RMS(h; g2)) -> (h, the routed layer's counts or
        None)."""
        x = rms_norm(h, w["g2"], self.eps)
        if ffn == "dense":
            with trace.device_scope("tower.ffn_dense"):
                out = swiglu(x, w["ffn"]["wg"], w["ffn"]["wu"],
                             w["ffn"]["wd"])
                if self.sandwich:
                    out = rms_norm(out, w["g2_post"], self.eps)
                return h + out, None
        live = jnp.arange(h.shape[1])[None, :] < lengths[:, None]
        out, counts = self.routed(w["ffn"], x, live)
        if self.sandwich:
            out = rms_norm(out, w["g2_post"], self.eps)
        return h + out, counts

    def head_terms(self, params, h, targets, negatives):
        """h [M, H] -> cross-entropy [M], log p of target and of negative
        [M]; token blocks under a checkpoint, as ``looplm``'s."""
        @jax.checkpoint
        def block(args):
            hb, yb, nb = args
            lse, zy, zn = rowlm.head_logits(params["head"], hb, yb, nb)
            return lse - zy, zy - lse, zn - lse

        return rowlm.map_token_blocks(block, HEAD_BLOCK, h, targets,
                                      negatives)

    def loss(self, params, rows, lengths, valid, seq_keys):
        x = rows[:, self.seq_key_slot]                        # [B, n, H]
        if self.input_scale != 1.0:
            x = x * self.input_scale
        ln = jnp.where(valid, lengths[:, self.seq_key_slot], 0)
        b, n, hd = x.shape
        targets, has_target, negatives = rowlm.next_token_plan(
            seq_keys, ln, valid, n, self.key_base, self.vocab, self.neg_seed)
        h, counts = x, []
        keep = jax.checkpoint_policies.save_only_these_names(ROUTED_OUT) \
            if self.keep_routed else None
        # a checkpoint a half layer: the backward recomputes a mixer or a
        # feed-forward, never both at once
        for (mixer, ffn), w in zip(self.layers, params["layers"]):
            h = self.mix(mixer, w, h, ln)
            h, c = jax.checkpoint(
                lambda w, h, ffn=ffn: self.feed_forward(ffn, w, h, ln),
                policy=keep)(w, h)
            if c is not None:
                counts.append(c)
        h = rms_norm(h, params["gf"], self.eps)
        ce, lp_pos, lp_neg = self.head_terms(
            params, h.reshape(b * n, hd), targets.reshape(-1),
            negatives.reshape(-1))
        with trace.device_scope("tower.head_loss"):
            wt = has_target.astype(jnp.float32)
            count = jnp.sum(wt)
            loss = jnp.sum(ce * wt) / jnp.maximum(count, 1.0)
            tokens = rowlm.token_counts(ln, valid, n)
            zero = jnp.zeros((), jnp.float32)
            held = sum((c["held"] for c in counts), zero)
            dropped = sum((c["dropped"] for c in counts), zero)
            # where a balancing bias moves: the choices over all experts
            # (behind the held experts' loads in the stats, and by routed
            # layer for after_update) and the spread of the bias the step
            # routed with
            route, spread = [], []
            if self.after_update is not None:
                route = [c["route"] for c in counts]
                bias = jnp.stack([w["ffn"]["router_bias"]
                                  for w, (_, f) in zip(params["layers"],
                                                       self.layers)
                                  if f == "moe"])
                spread = [(jnp.max(bias) - jnp.min(bias))[None]]
            aux = {
                **rowlm.auc_pairs(lp_pos, lp_neg, has_target, self.vocab),
                "stats": jnp.concatenate(
                    [jnp.stack([count, tokens, b * n - tokens, held,
                                dropped])]
                    + [c["load"] for c in counts] + route + spread),
            }
            if route:
                aux["route"] = jnp.stack(route)
        return loss, jax.lax.stop_gradient(aux)

    def balance_bias(self, params, aux):
        """The routing bias's own update, after Adam (the bias takes no
        gradient): with c_e the dispatched positions that chose expert e
        this step, over all experts, d_e = rate * sign(mean(c) - c_e) and
        bias_e += d_e - mean(d), a routed layer each."""
        with trace.device_scope("tower.moe_balance"):
            layers, i = list(params["layers"]), 0
            for l, (_, ffn) in enumerate(self.layers):
                if ffn != "moe":
                    continue
                c = aux["route"][i]
                i += 1
                d = self.balance_rate * jnp.sign(jnp.mean(c) - c)
                f = layers[l]["ffn"]
                layers[l] = {**layers[l], "ffn": {
                    **f, "router_bias": f["router_bias"] + d - jnp.mean(d)}}
        return {**params, "layers": layers}

    def record_stats(self, total, steps: int) -> None:
        """Counters of a pass: ``total`` is ``stats`` summed over its
        ``steps`` steps (the trainer reads it back once a pass)."""
        _, valid, padded, held, dropped = (
            float(v) for v in total[:len(STATS)])
        rowlm.record_padding(valid, padded)
        if not self.moe_layers:
            return
        n_load = len(self.experts_held) * self.moe_layers
        load = [float(v) for v in total[len(STATS):len(STATS) + n_load]]
        stat_add("tower.moe.assignments_held", held)
        stat_add("tower.moe.assignments", valid * self.top_k
                 * self.moe_layers)
        stat_add("tower.moe.dropped_assignments", dropped)
        # tokens an expert of this chip received over the pass, the
        # busiest (layer, expert) and the mean
        stat_add("tower.moe.expert_load_max", max(load))
        stat_add("tower.moe.expert_load_mean", sum(load) / len(load))
        if self.after_update is None:
            return
        # the positions that chose an expert over the pass, the busiest
        # (layer, expert of all of them) and the mean; the bias's spread
        # (max - min over the routed layers) as the pass's steps routed
        # with it, their mean
        route = [float(v) for v in total[len(STATS) + n_load:-1]]
        stat_add("tower.moe.route_load_max", max(route))
        stat_add("tower.moe.route_load_mean", sum(route) / len(route))
        stat_set("tower.moe.bias_range", float(total[-1]) / max(steps, 1))


class HybridLM(RoutedLM):
    """Kimi Linear's tower (module docstring): KDA and latent-attention
    mixers over ``RoutedLM``'s loop."""

    def __init__(self, hidden: int, layers: Sequence[Tuple[str, str]],
                 vocab: int, *, kda_heads: int, kda_head_dim: int,
                 conv_kernel: int, gate_rank: int, mla_heads: int,
                 kv_rank: int, qk_nope: int, qk_rope: int, v_dim: int,
                 ffn: int, experts: int, experts_held: Sequence[int],
                 top_k: int, expert_ffn: int, shared_experts: int,
                 routed_scale: float, eps: float = 1e-5,
                 init_std: float = 0.02, key_base: int = 1,
                 neg_seed: int = 0):
        """``layers``: a (mixer, ffn) pair a layer, mixer ``kda`` | ``mla``,
        ffn ``dense`` | ``moe``.  ``experts`` is the router's width,
        ``experts_held`` the ids of the experts this chip holds."""
        super().__init__(
            hidden, layers, vocab, ffn=ffn, experts=experts,
            experts_held=experts_held, top_k=top_k, expert_ffn=expert_ffn,
            shared_experts=shared_experts, routed_scale=routed_scale,
            eps=eps, init_std=init_std, key_base=key_base,
            neg_seed=neg_seed)
        self.kda_heads, self.kda_head_dim = kda_heads, kda_head_dim
        self.conv_kernel, self.gate_rank = conv_kernel, gate_rank
        self.mla_heads, self.kv_rank = mla_heads, kv_rank
        self.qk_nope, self.qk_rope, self.v_dim = qk_nope, qk_rope, v_dim

    def init_mixer(self, kind, w, ones, keys):
        h = self.hidden
        if kind == "mla":
            nh = self.mla_heads
            return {"wq": w(h, nh * (self.qk_nope + self.qk_rope)),
                    "wkva": w(h, self.kv_rank + self.qk_rope),
                    "g_c": ones(self.kv_rank),
                    "wkvb": w(self.kv_rank, nh * (self.qk_nope + self.v_dim)),
                    "wo": w(nh * self.v_dim, h)}
        nh, d = self.kda_heads, self.kda_head_dim
        a, r, ck = nh * d, self.gate_rank, self.conv_kernel

        def conv():     # PyTorch's Conv1d default: U(+-1/sqrt(kernel))
            lim = 1.0 / math.sqrt(ck)
            return jax.random.uniform(next(keys), (ck, a), jnp.float32,
                                      -lim, lim)

        # A in U(1, 16), the step dt log-uniform in [1e-3, 1e-1] and
        # dt_bias its inverse softplus
        dt = jnp.exp(jax.random.uniform(
            next(keys), (a,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        return {"wq": w(h, a), "wk": w(h, a), "wv": w(h, a),
                "cq": conv(), "ck": conv(), "cv": conv(),
                "wf1": w(h, r), "wf2": w(r, a),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (nh,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "wb": w(h, nh), "wg1": w(h, r), "wg2": w(r, a),
                "g_o": ones(d), "wo": w(a, h)}

    def seqs_a_group(self, kind):
        """A KDA mixer takes ``KDA_SEQS`` sequences at a time: its q, k,
        v, decays, gates and their chunked copies are a few dozen
        [tokens, heads x d] arrays; latent attention the whole batch."""
        return KDA_SEQS if kind == "kda" else None

    def mixer(self, kind, w, a, lengths):
        return self.kda(w, a) if kind == "kda" else self.mla(w, a, lengths)

    def kda(self, w, a):
        """KDA's mixer on a = RMS(h; g1) [B, n, H]."""
        b, n, _ = a.shape
        nh, d = self.kda_heads, self.kda_head_dim

        def heads(z):
            return z.reshape(b, n, nh, d)

        q = heads(jax.nn.silu(causal_conv(a @ w["wq"], w["cq"])))
        k = heads(jax.nn.silu(causal_conv(a @ w["wk"], w["ck"])))
        v = heads(jax.nn.silu(causal_conv(a @ w["wv"], w["cv"])))
        q = l2_normalise(q) * d ** -0.5
        k = l2_normalise(k)
        g = -jnp.exp(w["a_log"])[:, None] * heads(jax.nn.softplus(
            (a @ w["wf1"]) @ w["wf2"] + w["dt_bias"]))
        beta = jax.nn.sigmoid(a @ w["wb"])                 # [B, n, heads]
        o = kda_chunked(q, k, v, g, beta)
        gate = jax.nn.sigmoid(heads((a @ w["wg1"]) @ w["wg2"]))
        o = rms_norm(o, w["g_o"], self.eps) * gate
        return o.reshape(b, n, nh * d) @ w["wo"]

    def mla(self, w, a, lengths):
        """Latent attention's mixer on a [B, n, H]."""
        b, n, _ = a.shape
        nh, dn, dr, dv = self.mla_heads, self.qk_nope, self.qk_rope, \
            self.v_dim
        q = (a @ w["wq"]).reshape(b, n, nh, dn + dr)
        ckv = a @ w["wkva"]
        kv = (rms_norm(ckv[..., :self.kv_rank], w["g_c"], self.eps)
              @ w["wkvb"]).reshape(b, n, nh, dn + dv)
        o = mla_attention(q[..., :dn], q[..., dn:], kv[..., :dn],
                          ckv[..., self.kv_rank:], kv[..., dn:], lengths)
        return o.reshape(b, n, nh * dv) @ w["wo"]
