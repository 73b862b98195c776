"""sorted_spmm: the MXU one-hot gather/scatter vs dense numpy references.

Runs the Pallas kernels in interpret mode on CPU (conftest pins cpu), with
small CHUNK/TILE geometry so worklist edge cases (gaps, boundary-shared
tiles, heavy skew, sentinel padding) are all hit.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddlebox_tpu.ops import sorted_spmm as sp


def _run(rows_np, n_rows, w=16, chunk=8, tile=32, seed=0, trim=False):
    """Gather + scatter through a freshly-built plan, diffed against the
    dense reference — THE single verification body for the named cases
    and the fuzz.  trim=True builds a trimmed plan (row 0 is then the
    reserved zero row and excluded from the comparisons)."""
    p = len(rows_np)
    dims = sp.spmm_dims(p, n_rows, chunk=chunk, tile=tile)
    eff = sp.trimmed_dims(dims, int((rows_np != 0).sum())) if trim else None
    if eff is not None and eff.p_pad >= dims.p_pad:
        eff = None                         # nothing to trim at this draw
    kd = eff or dims
    lo_row = 1 if eff is not None else 0
    rng = np.random.default_rng(seed)
    table = np.zeros((w, dims.n_kernel), np.float32)
    table[:, lo_row:n_rows] = rng.normal(
        0, 1, (w, n_rows - lo_row)).astype(np.float32)
    payload = rng.normal(0, 1, (w, p)).astype(np.float32)

    rows = jnp.asarray(rows_np, jnp.int32)
    rows2d, perm, inv_perm, ch, tl, fg, fs, first_occ = sp.build_plan(
        rows, dims, eff)

    # first_occ marks exactly the first occurrence of each sorted run
    srt = np.asarray(rows2d).reshape(-1)
    exp_first = np.concatenate([[1.0], (srt[1:] != srt[:-1]).astype(
        np.float32)])
    assert np.array_equal(np.asarray(first_occ), exp_first)

    # permutation sanity (perm is always the full bijection)
    assert np.array_equal(np.asarray(perm)[np.asarray(inv_perm)
                                           + (dims.p_pad - kd.p_pad)]
                          if eff is not None else
                          np.asarray(perm)[np.asarray(inv_perm)],
                          np.arange(p))

    g = sp.gather_sorted(jnp.asarray(table), rows2d, ch, tl, fg, kd,
                         interpret=True)
    if eff is None:
        g_canon = np.asarray(g)[:, :p][:, np.asarray(inv_perm)].T
    else:
        iv = np.asarray(inv_perm)
        assert np.all(iv[rows_np != 0] >= 0), "a real occurrence dropped"
        g_canon = np.asarray(g).T[np.maximum(iv, 0)] * (iv >= 0)[:, None]
    np.testing.assert_allclose(g_canon, table[:, rows_np].T, atol=1e-3,
                               rtol=1e-3)

    if eff is None:
        srt_pay = payload.T[np.asarray(perm)]
        srt_pay = np.concatenate(
            [srt_pay, np.zeros((dims.p_pad - p, w), np.float32)])
    else:
        p0 = dims.p_pad - kd.p_pad
        perm_k = np.concatenate(
            [np.asarray(perm), np.zeros(dims.p_pad - p, np.int64)])[p0:]
        srt_pay = payload.T[perm_k.astype(np.int64)]
    d = sp.scatter_add_sorted(jnp.asarray(srt_pay.T), rows2d, ch, tl, fs,
                              kd, interpret=True)
    ref = np.zeros((w, dims.n_kernel), np.float32)
    np.add.at(ref.T, rows_np, payload.T)
    np.testing.assert_allclose(np.asarray(d)[:, lo_row:n_rows],
                               ref[:, lo_row:n_rows], atol=1e-2, rtol=1e-3)
    # untouched rows must be exactly zero (optimizer masks depend on it)
    untouched = np.setdiff1d(np.arange(lo_row, n_rows), rows_np)
    assert np.all(np.asarray(d)[:, untouched] == 0.0)


def test_uniform_random():
    rng = np.random.default_rng(1)
    _run(rng.integers(0, 200, 300).astype(np.int32), 200)


def test_heavy_skew_single_row():
    rows = np.full(300, 7, np.int32)  # every occurrence on one row
    _run(rows, 200)


def test_skew_two_extremes():
    rows = np.concatenate([np.zeros(150, np.int32),
                           np.full(150, 199, np.int32)])
    _run(rows, 200)


def test_sparse_gaps():
    # few occurrences scattered over a big table -> inter-chunk tile gaps
    rows = np.array([3, 500, 501, 1999], np.int32)
    _run(rows, 2000)


def test_tiny_batch():
    _run(np.array([5], np.int32), 64)


def test_unsorted_input_order():
    rng = np.random.default_rng(3)
    rows = rng.permutation(np.repeat(np.arange(50, dtype=np.int32), 4))
    _run(rows, 64)


def test_non_multiple_sizes():
    # p not multiple of chunk, n_rows not multiple of tile
    rng = np.random.default_rng(4)
    _run(rng.integers(0, 77, 59).astype(np.int32), 77, chunk=8, tile=32)


def test_trimmed_plan_matches_untrimmed():
    """Trimming drops only row-0 (padding) occurrences: gather values match
    the full dense reference after the mask, scatter deltas match on every
    real row, untouched rows stay exactly zero."""
    rng = np.random.default_rng(5)
    p, n_rows, w, chunk, tile = 300, 200, 16, 8, 32
    rows_np = rng.integers(1, n_rows, p).astype(np.int32)
    rows_np[rng.random(p) < 0.4] = 0        # heavy padding fraction
    dims = sp.spmm_dims(p, n_rows, chunk=chunk, tile=tile)
    n_real = int((rows_np != 0).sum())
    eff = sp.trimmed_dims(dims, n_real)
    assert eff.p_pad < dims.p_pad
    assert eff.p_pad % chunk == 0 and eff.n_work < dims.n_work

    table = np.zeros((w, dims.n_kernel), np.float32)
    # row 0 is the reserved zero row — the mask reproduces exactly that
    table[:, 1:n_rows] = rng.normal(0, 1, (w, n_rows - 1)).astype(np.float32)
    payload = rng.normal(0, 1, (w, p)).astype(np.float32)

    rows2d, perm, inv_perm, ch, tl, fg, fs, first_occ = sp.build_plan(
        jnp.asarray(rows_np), dims, eff)
    assert rows2d.shape[0] == eff.n_chunks
    assert perm.shape[0] == p and ch.shape[0] == eff.n_work
    iv = np.asarray(inv_perm)
    assert np.all(iv[rows_np != 0] >= 0), "a real occurrence was dropped"
    assert np.all(iv < eff.p_pad)
    # perm stays the full bijection: suffix = kept positions
    p0 = dims.p_pad - eff.p_pad
    perm_k = np.concatenate(
        [np.asarray(perm), np.zeros(dims.p_pad - p, np.int64)])[p0:]

    g = sp.gather_sorted(jnp.asarray(table), rows2d, ch, tl, fg, eff,
                         interpret=True)
    v = np.asarray(g).T[np.maximum(iv, 0)] * (iv >= 0)[:, None]
    np.testing.assert_allclose(v, table[:, rows_np].T, atol=1e-4, rtol=1e-4)

    srt = payload.T[perm_k.astype(np.int64)]     # [eff.p_pad, w]
    d = sp.scatter_add_sorted(jnp.asarray(srt.T), rows2d, ch, tl, fs, eff,
                              interpret=True)
    ref = np.zeros((w, dims.n_kernel), np.float32)
    np.add.at(ref.T, rows_np, payload.T)
    np.testing.assert_allclose(np.asarray(d)[:, 1:n_rows], ref[:, 1:n_rows],
                               atol=1e-3, rtol=1e-4)
    untouched = np.setdiff1d(np.arange(1, n_rows), rows_np)
    assert np.all(np.asarray(d)[:, untouched] == 0.0)


def test_trimmed_dims_no_padding_degenerates():
    # when every occurrence is real, trimming keeps everything
    dims = sp.spmm_dims(256, 1000, chunk=8, tile=32)
    eff = sp.trimmed_dims(dims, 256)
    assert eff == dims


def test_fuzz_random_geometries():
    """Property fuzz: random (p, n_rows, chunk, tile, zero-fraction, skew,
    trim) draws through the shared _run verification body."""
    rng = np.random.default_rng(42)
    for trial in range(12):
        chunk = int(rng.choice([4, 8, 16]))
        tile = int(rng.choice([16, 32, 64]))
        p = int(rng.integers(1, 400))
        n_rows = int(rng.integers(2, 1500))
        if rng.random() < 0.3:   # heavy skew: few distinct rows
            rows = rng.choice(
                rng.integers(1, n_rows, size=max(1, n_rows // 50)), size=p)
        else:
            rows = rng.integers(0, n_rows, size=p)
        rows = rows.astype(np.int32)
        rows[rng.random(p) < float(rng.random()) * 0.6] = 0
        _run(rows, n_rows, w=int(rng.integers(1, 9)), chunk=chunk,
             tile=tile, seed=trial, trim=bool(rng.random() < 0.5))


# -- a width of several W blocks (a sequence model's 2052-wide row) ---------

@pytest.mark.parametrize("w,trim", [(384, False), (384, True), (256, False)])
def test_wide_rows_take_several_blocks_and_match_the_dense_forms(w, trim):
    """Past W_BLOCK feature rows both kernels cut W into blocks along a
    leading grid axis; the one-hot does not depend on it, so the result
    is the narrow kernels': equal to the dense reference (``_run``) and to
    the ``_xla`` forms."""
    assert w > sp.W_BLOCK and sp.padded_width(w) == w
    rng = np.random.default_rng(11)
    rows = rng.integers(1, 200, 300).astype(np.int32)
    if trim:
        rows[:120] = 0
    _run(rows, 200, w=w, trim=trim)
    dims = sp.spmm_dims(len(rows), 200, chunk=8, tile=32)
    plan = sp.build_plan(jnp.asarray(rows), dims)
    rows2d, _, _, ch, tl, fg, fs, _ = plan
    table = np.zeros((w, dims.n_kernel), np.float32)    # sentinel tile: 0
    table[:, :200] = rng.normal(0, 1, (w, 200))
    table = jnp.asarray(table)
    pay = jnp.asarray(rng.normal(0, 1, (w, dims.p_pad)), jnp.float32)
    np.testing.assert_allclose(
        sp.gather_sorted(table, rows2d, ch, tl, fg, dims, interpret=True),
        sp.gather_sorted_xla(table, rows2d, ch, tl, fg, dims),
        atol=1e-3, rtol=1e-3)
    got = sp.scatter_add_sorted(pay, rows2d, ch, tl, fs, dims,
                                interpret=True)
    want = sp.scatter_add_sorted_xla(pay, rows2d, ch, tl, fs, dims)
    keep = slice(0, dims.n_kernel - dims.tile)     # not the sentinel tile
    np.testing.assert_allclose(got[:, keep], want[:, keep], atol=1e-2,
                               rtol=1e-3)


def _grids(w):
    """The grids of the two kernels' pallas_calls at table width ``w``."""
    dims = sp.spmm_dims(64, 100, chunk=8, tile=32)
    i32 = jnp.int32
    plan = (jnp.zeros((dims.n_chunks, 1, 8), i32),) + tuple(
        jnp.zeros((dims.n_work,), i32) for _ in range(3))
    out = []
    for fn, cols in ((sp.gather_sorted, dims.n_kernel),
                     (sp.scatter_add_sorted, dims.p_pad)):
        jaxpr = jax.make_jaxpr(lambda a, r, c, t, f: fn(
            a, r, c, t, f, dims, interpret=True))(
            jnp.zeros((w, cols), jnp.float32), *plan)
        call = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert len(call) == 1
        out.append(tuple(call[0].params["grid_mapping"].grid))
    return dims, out


@pytest.mark.parametrize("w", [12, 36])
def test_ctr_widths_lower_as_before(w):
    """The accepted configurations' tables (W = 12, 36) stay one block of
    rows: a one-axis grid over the worklist, no pad, the programs they
    had."""
    dims, grids = _grids(w)
    assert sp.padded_width(w) == w
    assert grids == [(dims.n_work,), (dims.n_work,)]


def test_a_2052_wide_table_runs_a_grid_over_its_rows():
    """Built at ``padded_width`` (the caller's part, ``mxu_path``): 17
    blocks of rows; a height that is no whole number of blocks is
    refused, not padded by a copy."""
    assert sp.padded_width(2052) == 2176 and sp.padded_width(300) == 384
    dims, grids = _grids(2176)
    assert grids == [(17, dims.n_work), (17, dims.n_work)]
    with pytest.raises(ValueError, match="whole blocks"):
        _grids(2052)
