"""The pass's key dedup: the native range-split dedup over the readers'
key chunks in place (native/hash_shard.cc ``pbox_dedup_*``) against the
``np.unique`` it replaced, and the engine's ``end_feed_pass`` on both."""

import numpy as np
import pytest

from paddlebox_tpu.config import EmbeddingTableConfig
from paddlebox_tpu.native import hash_map
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.utils.monitor import StatRegistry, stat_get

pytestmark = pytest.mark.skipif(not hash_map.available(),
                                reason="native lib failed to build")

U64 = np.uint64


def _want(chunks):
    allk = np.concatenate(chunks) if chunks else np.empty((0,), U64)
    u = np.unique(allk)
    return u[u != 0]


def _dense_slot_keys(rng, n_rec, vocab, exponent=1.1):
    """Keys as benchmark/harness/slotdata.py draws them: Zipf ranks within
    each field's contiguous range, record-major over the fields."""
    vocab = np.asarray(vocab, np.int64)
    offsets = np.concatenate([[0], np.cumsum(vocab)[:-1]])
    slots = np.tile(np.arange(len(vocab)), n_rec)
    a = 1.0 - exponent
    v = vocab[slots].astype(np.float64)
    x = np.exp(np.log1p(rng.random(len(slots)) * (np.power(v + 1.0, a) - 1.0))
               / a)
    ranks = np.minimum(x, v).astype(np.int64) - 1
    return (1 + offsets[slots] + (ranks * 2654435761) % vocab[slots]
            ).astype(U64)


def _chunks(case, rng):
    if case == "none":
        return []
    if case == "empty_chunk":
        return [np.empty((0,), U64)]
    if case == "all_zeros":
        return [np.zeros(70_000, U64), np.zeros(5, U64)]
    if case == "one_chunk":
        return [rng.integers(0, 1000, 90_000).astype(U64)]
    if case == "many_chunks":
        # ~500 chunks whose keys repeat across chunks, some chunks empty
        return [rng.integers(0, 40_000, rng.integers(0, 400)).astype(U64)
                for _ in range(500)]
    if case == "dense_slots":
        vocab = [1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3,
                 93145, 5683, 8351593, 3194, 27, 14992, 5461306]
        return [_dense_slot_keys(rng, 512, vocab) for _ in range(40)]
    if case == "hashed_u64":
        # 64-bit feasigns, half of them >= 2**63: unsigned order matters
        base = rng.integers(0, 2**63, 30_000, dtype=np.int64).astype(U64)
        keys = np.concatenate([base, base | U64(1 << 63),
                               np.array([2**64 - 1, 2**63, 1, 0], U64)])
        return [rng.choice(keys, 20_000) for _ in range(12)]
    if case == "few_distinct":
        # fewer distinct keys than threads: fewer ranges than asked for
        return [rng.integers(0, 6, 50_000).astype(U64) for _ in range(3)]
    if case == "zipf_hot":
        # one key is ~60% of the input
        z = rng.zipf(1.3, 400_000).astype(U64) * U64(0x9E3779B97F4A7C15)
        z[rng.random(len(z)) < 0.6] = U64(12345)
        return np.array_split(z, 7)
    raise ValueError(case)


CASES = ("none", "empty_chunk", "all_zeros", "one_chunk", "many_chunks",
         "dense_slots", "hashed_u64", "few_distinct", "zipf_hot")


@pytest.mark.parametrize("n_threads", [1, 2, 8, 64])
@pytest.mark.parametrize("case", CASES)
def test_native_dedup_equals_np_unique(case, n_threads):
    rng = np.random.default_rng(len(case) * 1000 + n_threads)
    chunks = _chunks(case, rng)
    got = hash_map.KeyDedup()(chunks, n_threads=n_threads)
    want = _want(chunks)
    assert got.dtype == want.dtype == U64
    np.testing.assert_array_equal(got, want)


def test_native_dedup_scratch_kept_across_calls():
    """One object over passes that grow, shrink and change key space: the
    kept sets start every call empty, whatever the last one left."""
    d = hash_map.KeyDedup()
    rng = np.random.default_rng(7)
    for case in ("dense_slots", "one_chunk", "hashed_u64", "none",
                 "zipf_hot", "dense_slots", "all_zeros", "many_chunks"):
        chunks = _chunks(case, rng)
        np.testing.assert_array_equal(d(chunks, n_threads=8), _want(chunks))


def test_native_dedup_reads_strided_chunks():
    base = np.arange(1, 200_001, dtype=U64)
    chunks = [base[::3], base[1::7], base[::-1]]
    np.testing.assert_array_equal(hash_map.KeyDedup()(chunks), _want(chunks))


def _engine_pass(native: bool):
    StatRegistry.instance().reset()
    eng = BoxPSEngine(EmbeddingTableConfig(embedding_dim=4, shard_num=4),
                      seed=3)
    if not native:
        eng._key_dedup = None  # as when the library does not load
    rng = np.random.default_rng(11)
    eng.begin_feed_pass()
    for c in _chunks("many_chunks", rng) + _chunks("hashed_u64", rng):
        eng.add_keys(c)
    eng.end_feed_pass()
    keys = eng.mapper.sorted_keys
    rows = eng.table.bulk_pull(keys)
    counts = (stat_get("ps.engine.dedup_keys_in"),
              stat_get("ps.engine.dedup_keys_native"))
    return keys, rows, counts


def test_end_feed_pass_native_equals_fallback():
    keys_n, rows_n, (n_in, n_native) = _engine_pass(True)
    assert n_in > 0 and n_native == n_in
    keys_f, rows_f, (f_in, f_native) = _engine_pass(False)
    assert f_in == n_in and f_native == 0
    assert keys_n.dtype == keys_f.dtype == U64
    np.testing.assert_array_equal(keys_n, keys_f)
    assert rows_n.keys() == rows_f.keys()
    for f in rows_n:
        np.testing.assert_array_equal(rows_n[f], rows_f[f])
