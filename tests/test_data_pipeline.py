import os

import numpy as np
import pytest

from paddlebox_tpu.config import DataFeedConfig, SlotConfig
from paddlebox_tpu.data.data_feed import DataFeed, SlotParser, parse_logkey
from paddlebox_tpu.data.batch_pack import BatchPacker
from paddlebox_tpu.data.dataset import SlotDataset, LoopbackTransport
from paddlebox_tpu.data.slot_record import BlockStore, SlotRecordBlock
from paddlebox_tpu.utils.monitor import StatRegistry, stat_snapshot


def make_config():
    return DataFeedConfig(
        slots=(
            SlotConfig("label", dtype="float", is_dense=True, dim=1),
            SlotConfig("dense0", dtype="float", is_dense=True, dim=3),
            SlotConfig("slot_a", slot_id=1, capacity=3),
            SlotConfig("slot_b", slot_id=2, capacity=2),
        ),
        batch_size=4,
    )


def write_slot_file(path, rows):
    """rows: list of (label, dense3, a_keys, b_keys)"""
    with open(path, "w") as f:
        for label, dense, a, b in rows:
            parts = [f"1 {label}", f"3 " + " ".join(str(d) for d in dense),
                     f"{len(a)} " + " ".join(str(k) for k in a),
                     f"{len(b)} " + " ".join(str(k) for k in b)]
            f.write(" ".join(parts) + "\n")


ROWS = [
    (1, [0.1, 0.2, 0.3], [11, 12], [21]),
    (0, [0.4, 0.5, 0.6], [13], [22, 23]),
    (1, [0.7, 0.8, 0.9], [14, 15, 16, 17], [24]),  # slot_a overflows cap 3
    (0, [1.0, 1.1, 1.2], [18], [25]),
    (1, [1.3, 1.4, 1.5], [19], [26]),
]


@pytest.fixture
def slot_file(tmp_path):
    p = tmp_path / "part-00000"
    write_slot_file(p, ROWS)
    return str(p)


def test_parse_block(slot_file):
    cfg = make_config()
    feed = DataFeed(cfg, use_native=False)
    blocks = list(feed.read_file(slot_file))
    block = SlotRecordBlock.concat(blocks)
    assert block.n == 5
    vals, off = block.uint64_slots["slot_a"]
    assert list(off) == [0, 2, 3, 7, 8, 9]
    assert list(vals) == [11, 12, 13, 14, 15, 16, 17, 18, 19]
    lv, lo = block.float_slots["label"]
    np.testing.assert_allclose(lv, [1, 0, 1, 0, 1])
    assert block.feasign_count == 15  # 9 in slot_a + 6 in slot_b


def test_parse_ins_id_and_logkey():
    cfg = DataFeedConfig(slots=(SlotConfig("s", capacity=1),))
    parser = SlotParser(cfg, parse_ins_id=True, parse_logkey=True)
    # ins_id then logkey: search_id=0xabc, cmatch=0x01, rank=0x02
    block = parser.parse_block(["1 insX 1 abc0102 1 42"])
    assert block.ins_ids == ["insX"]
    assert int(block.search_ids[0]) == 0xabc
    assert int(block.cmatch[0]) == 1
    assert int(block.rank[0]) == 2
    assert parse_logkey("abc0102") == (0xabc, 1, 2)


def test_select_and_concat():
    cfg = make_config()
    parser = SlotParser(cfg)
    lines = []
    for label, dense, a, b in ROWS:
        lines.append(" ".join([
            f"1 {label}", "3 " + " ".join(map(str, dense)),
            f"{len(a)} " + " ".join(map(str, a)),
            f"{len(b)} " + " ".join(map(str, b))]))
    block = parser.parse_block(lines)
    sel = block.select(np.array([2, 0]))
    vals, off = sel.uint64_slots["slot_a"]
    assert list(vals) == [14, 15, 16, 17, 11, 12]
    assert list(off) == [0, 4, 6]
    back = SlotRecordBlock.concat([sel, block.select(np.array([1]))])
    assert back.n == 3


def test_dataset_load_shuffle_batches(slot_file, tmp_path):
    cfg = make_config()
    p2 = tmp_path / "part-00001"
    write_slot_file(p2, ROWS[:2])
    ds = SlotDataset(cfg, read_threads=2)
    ds.set_filelist([slot_file, str(p2)])
    seen_keys = []
    ds.register_key_consumer(lambda ks: seen_keys.append(ks.copy()))
    ds.load_into_memory()
    assert ds.instance_num() == 7
    total_keys = np.concatenate(seen_keys)
    assert len(total_keys) == 15 + 6  # feasigns from both files
    ds.local_shuffle()
    assert ds.instance_num() == 7
    batches = list(ds.batches(4))
    assert [b.n for b in batches] == [4, 3]
    batches = list(ds.batches(4, drop_last=True))
    assert [b.n for b in batches] == [4]


def test_global_shuffle_loopback():
    cfg = DataFeedConfig(slots=(SlotConfig("s", capacity=2),))
    parser = SlotParser(cfg)
    world = LoopbackTransport.make_world(2)
    datasets = []
    for r in range(2):
        ds = SlotDataset(cfg, transport=world[r])
        lines = [f"1 {100 * r + i}" for i in range(10)]
        ds._blocks = [parser.parse_block(lines)]
        datasets.append(ds)
    import threading
    threads = [threading.Thread(target=ds.global_shuffle) for ds in datasets]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    all_keys = []
    for ds in datasets:
        for b in ds.get_blocks():
            all_keys.extend(b.uint64_slots["s"][0].tolist())
    assert sorted(all_keys) == sorted(
        [100 * r + i for r in range(2) for i in range(10)])
    assert datasets[0].instance_num() + datasets[1].instance_num() == 20


def test_batch_pack(slot_file):
    cfg = make_config()
    feed = DataFeed(cfg, use_native=False)
    block = SlotRecordBlock.concat(list(feed.read_file(slot_file)))
    packer = BatchPacker(cfg, batch_size=8, label_slot="label")
    key_map = {0: 0, 11: 1, 12: 2, 13: 3, 14: 4, 15: 5, 16: 6, 17: 7,
               18: 8, 19: 9, 21: 10, 22: 11, 23: 12, 24: 13, 25: 14, 26: 15}
    mapper = np.vectorize(lambda k: key_map.get(int(k), 0))
    batch = packer.pack(block, key_mapper=lambda ks: mapper(ks))
    S, B, L = batch.indices.shape
    assert (S, B, L) == (2, 8, 3)
    assert batch.num_real == 5
    assert batch.valid.sum() == 5
    # slot_a row 2 overflows capacity: clipped to 3
    assert batch.lengths[0, 2] == 3
    assert list(batch.indices[0, 2]) == [4, 5, 6]
    # slot_b row 1: two keys then padding 0
    assert list(batch.indices[1, 1]) == [11, 12, 0]
    np.testing.assert_allclose(batch.labels[:5], [1, 0, 1, 0, 1])
    np.testing.assert_allclose(batch.dense[0], [0.1, 0.2, 0.3])
    assert batch.dense.shape == (8, 3)


def test_preload(slot_file):
    cfg = make_config()
    ds = SlotDataset(cfg)
    ds.set_filelist([slot_file])
    ds.preload_into_memory()
    ds.wait_preload_done()
    assert ds.instance_num() == 5
    ds.release_memory()
    assert ds.instance_num() == 0


def test_pv_aligned_batches():
    """After preprocess_instance, batches cut at page-view boundaries — a
    search_id never straddles two batches (≙ SlotPvInstance batching,
    data_set.cc:2648)."""
    from paddlebox_tpu.data.slot_record import SlotRecordBlock

    rng = np.random.default_rng(0)
    n = 50
    blk = SlotRecordBlock(n=n)
    blk.uint64_slots["s0"] = (
        rng.integers(1, 100, size=n).astype(np.uint64),
        np.arange(n + 1, dtype=np.int64))
    blk.float_slots["label"] = (
        rng.integers(0, 2, size=n).astype(np.float32),
        np.arange(n + 1, dtype=np.int64))
    # 12 page views of sizes 1..8, shuffled record order
    sizes = rng.integers(1, 9, size=12)
    sid = np.repeat(np.arange(1, 13, dtype=np.uint64), sizes)[:n]
    sid = np.pad(sid, (0, max(0, n - len(sid))), constant_values=12)
    perm = rng.permutation(n)
    blk.search_ids = sid[perm][:n]

    cfg = DataFeedConfig(slots=(
        SlotConfig("label", dtype="float", is_dense=True, dim=1),
        SlotConfig("s0", slot_id=100, capacity=1)))
    ds = SlotDataset(cfg)
    ds._blocks = [blk]
    ds.preprocess_instance()

    B = 16
    seen = []
    for batch in ds.batches(B):
        assert 0 < batch.n <= B
        ids = batch.search_ids
        seen.append(ids)
    flat = np.concatenate(seen)
    assert len(flat) == n                       # every record exactly once
    # no search_id spans two batches
    for a, b in zip(seen[:-1], seen[1:]):
        assert a[-1] != b[0]
    # leaving pv mode restores fixed-size batching
    ds.postprocess_instance()
    sizes2 = [bt.n for bt in ds.batches(B)]
    assert sizes2[:-1] == [B] * (len(sizes2) - 1)


# -- the reader's two carriers: a file's bytes handed to the native parser
# as they lie, against the text loop with the same parser ------------------

CHUNK = 16
BUFFER_SIZES = [64, 4096, 1 << 20]


class LinesOnly:
    """The same parser, offering ``parse_block(lines)`` alone: its feed
    takes the text loop (the choice is made from what the parser offers)."""

    def __init__(self, parser):
        self.parse_block = parser.parse_block


def record(rng, ins_id=False, logkey=False, wide=False):
    parts = []
    if ins_id:
        parts.append(f"1 ins{rng.integers(0, 10**6)}")
    if logkey:
        parts.append("1 %x%02x%02x" % (rng.integers(1, 1 << 40),
                                       rng.integers(0, 256),
                                       rng.integers(0, 256)))
    parts.append(f"1 {rng.integers(0, 2)}")
    parts.append("3 " + " ".join(
        rng.choice(["%.4f", "%.3e", "-%.2f", "%.0f"]) % abs(rng.normal())
        for _ in range(3)))
    for cap in (40 if wide else 4, 2):
        k = rng.integers(1, cap + 1)
        parts.append(f"{k} " + " ".join(
            str(rng.integers(1, 1 << 62)) for _ in range(k)))
    return " ".join(parts)


def records(n, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [record(rng, **kw) for _ in range(n)]


def _blank_lines():
    out = ["", "   ", "\t"]
    for k, r in enumerate(records(40, 1)):
        out.append(r)
        if k % 5 == 0:
            out.extend(["", " \t "])
    # blank lines where a chunk ends and where the file does
    return "\n".join(out[:3] + out[3:] + ["", "", ""]) + "\n"


def _blanks_and_tabs():
    return "".join(
        ["", "  ", "\t", " \t "][k % 4] + r.replace(" ", "  ", 2)
        + ["", " ", "\t", " \t  "][(k // 4) % 4] + "\n"
        for k, r in enumerate(records(50, 2)))


# name -> (the file's text, DataFeed keywords)
CASES = {
    "plain": lambda: ("\n".join(records(50)) + "\n", {}),
    "blank_lines": lambda: (_blank_lines(), {}),
    "leading_trailing_blanks_and_tabs": lambda: (_blanks_and_tabs(), {}),
    "crlf": lambda: ("\r\n".join(records(50, 3)) + "\r\n", {}),
    "no_final_newline": lambda: ("\n".join(records(50, 4)), {}),
    "no_final_newline_trailing_blank": lambda: (
        "\n".join(records(21, 5)) + " \t", {}),
    "exactly_chunk_lines": lambda: ("\n".join(records(CHUNK, 6)) + "\n", {}),
    "chunk_lines_plus_one": lambda: (
        "\n".join(records(CHUNK + 1, 7)) + "\n", {}),
    "two_chunks_then_blank_lines": lambda: (
        "\n".join(records(2 * CHUNK, 8)) + "\n\n  \n\n", {}),
    "one_record": lambda: (records(1, 9)[0], {}),
    "empty_file": lambda: ("", {}),
    "blank_lines_only": lambda: ("\n  \n\t\n\r\n", {}),
    "parse_ins_id": lambda: (
        "\n".join(records(50, 10, ins_id=True)) + "\n",
        {"parse_ins_id": True}),
    "parse_logkey": lambda: (
        "\n".join(records(50, 11, logkey=True)) + "\n",
        {"parse_logkey": True}),
    "parse_ins_id_and_logkey": lambda: (
        "\n".join(records(50, 12, ins_id=True, logkey=True)) + "\n",
        {"parse_ins_id": True, "parse_logkey": True}),
    "float_slots": lambda: (
        "".join(f"1 {k % 2} 3 {k}.5 -1e-3 {k * 1e7:.6e} 1 {k + 1} 1 7\n"
                for k in range(40)), {}),
    "multi_key_slots": lambda: (
        "\n".join(records(50, 13, wide=True)) + "\n", {}),
}


def write_case(name, tmp_path):
    text, kw = CASES[name]()
    path = tmp_path / "part-00000"
    path.write_bytes(text.encode())
    return str(path), kw


def feeds(cfg, buffer_bytes, **kw):
    """(the feed on the bytes path, the same parser on the text path)."""
    raw = DataFeed(cfg, chunk_lines=CHUNK, **kw)
    raw.buffer_bytes = buffer_bytes
    assert raw._parser.takes_bytes
    text = DataFeed(cfg, chunk_lines=CHUNK, **kw)
    text._parser = LinesOnly(raw._parser)
    return raw, text


def assert_blocks_equal(got, want):
    assert [b.n for b in got] == [b.n for b in want]
    for a, b in zip(got, want):
        for slots_a, slots_b in ((a.uint64_slots, b.uint64_slots),
                                 (a.float_slots, b.float_slots)):
            assert slots_a.keys() == slots_b.keys()
            for name in slots_a:
                for x, y in zip(slots_a[name], slots_b[name]):
                    assert x.dtype == y.dtype and np.array_equal(x, y), name
        assert a.ins_ids == b.ins_ids
        for field in ("search_ids", "cmatch", "rank"):
            x, y = getattr(a, field), getattr(b, field)
            assert (x is None) == (y is None)
            assert x is None or np.array_equal(x, y), field


def native_or_skip():
    from paddlebox_tpu.native import slot_parser
    if not slot_parser.available():
        pytest.skip("native lib not built")


@pytest.mark.parametrize("buffer_bytes", BUFFER_SIZES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_bytes_path_equals_text_path(case, buffer_bytes, tmp_path):
    native_or_skip()
    path, kw = write_case(case, tmp_path)
    raw, text = feeds(make_config(), buffer_bytes, **kw)
    want = list(text.read_file(path))
    assert_blocks_equal(list(raw.read_file(path)), want)
    assert all(b.n == CHUNK for b in want[:-1])


@pytest.mark.parametrize("buffer_bytes", BUFFER_SIZES)
@pytest.mark.parametrize("carrier", ["gz", "pipe_command"])
def test_bytes_path_equals_text_path_through_a_pipe(carrier, buffer_bytes,
                                                    tmp_path):
    native_or_skip()
    import dataclasses
    import gzip
    cfg = make_config()
    text_of_file = "\n".join(records(70, 14)) + "\n"
    if carrier == "gz":
        path = str(tmp_path / "part-00000.gz")
        with gzip.open(path, "wb") as f:
            f.write(text_of_file.encode())
        n = 70
    else:
        path = str(tmp_path / "part-00000")
        with open(path, "w") as f:
            f.write(text_of_file)
        cfg = dataclasses.replace(cfg, pipe_command="sed -n '2~2p'")
        n = 35
    raw, text = feeds(cfg, buffer_bytes)
    want = list(text.read_file(path))
    assert sum(b.n for b in want) == n
    assert_blocks_equal(list(raw.read_file(path)), want)


@pytest.mark.parametrize("buffer_bytes", BUFFER_SIZES)
def test_sample_rate_keeps_the_same_set_on_both_paths(buffer_bytes,
                                                      tmp_path, monkeypatch):
    native_or_skip()
    import dataclasses
    from paddlebox_tpu.native.slot_parser import NativeSlotParser
    cfg = dataclasses.replace(make_config(), sample_rate=0.4, rand_seed=5)
    paths = []
    for k in range(3):
        paths.append(str(tmp_path / f"part-{k:05d}"))
        with open(paths[-1], "w") as f:
            f.write("\n".join(records(9000, 20 + k)) + "\n")
    monkeypatch.setattr(DataFeed, "buffer_bytes", buffer_bytes)

    def kept():
        ds = SlotDataset(cfg, read_threads=1)
        ds.set_filelist(paths)
        ds.load_into_memory()
        return ds.get_blocks()

    got = kept()
    monkeypatch.setattr(NativeSlotParser, "takes_bytes", False)
    want = kept()
    assert 0 < sum(b.n for b in want) < 27000
    assert_blocks_equal(got, want)


@pytest.mark.parametrize("buffer_bytes", BUFFER_SIZES)
@pytest.mark.parametrize("fault", ["slot_count_zero", "values_missing",
                                   "ins_id_prefix"])
def test_a_malformed_record_raises_the_same_error(fault, buffer_bytes,
                                                  tmp_path):
    native_or_skip()
    good = records(2 * CHUNK + 3, 15, ins_id=fault == "ins_id_prefix")
    bad = {"slot_count_zero": "1 1 3 0.1 0.2 0.3 0 1 5",
           "values_missing": "1 1 3 0.1 0.2 0.3 2 8 9 1 \t",
           "ins_id_prefix": "2 insX 1 1 3 0.1 0.2 0.3 1 4 1 5"}[fault]
    path = tmp_path / "part-00000"
    path.write_text("\n".join(good[:CHUNK + 5] + [bad] + good[CHUNK + 5:])
                    + "\n")
    raw, text = feeds(make_config(), buffer_bytes,
                      parse_ins_id=fault == "ins_id_prefix")
    seen, errors = [], []
    for feed in (raw, text):
        blocks = []
        with pytest.raises(ValueError) as err:
            for block in feed.read_file(str(path)):
                blocks.append(block)
        seen.append(blocks)
        errors.append(str(err.value))
    assert errors[0] == errors[1] and "native parse failed" in errors[0]
    assert [b.n for b in seen[1]] == [CHUNK]    # the chunks before it
    assert_blocks_equal(seen[0], seen[1])


def _plugin_so_without_the_bytes_entry(tmp_path):
    """A site's parser .so written against the block ABI alone: this
    repo's parser source with the bytes entry's symbol renamed away."""
    import subprocess
    from paddlebox_tpu.native import build
    src = os.path.join(os.path.dirname(build.lib_path()), "slot_parser.cc")
    so = str(tmp_path / "libsite_parser.so")
    subprocess.run(["g++", "-O1", "-shared", "-fPIC", "-std=c++17",
                    "-Dpbox_parse_block_bytes=site_private_entry",
                    "-o", so, src], check=True, capture_output=True)
    return so


def _string_slot_feed():
    from paddlebox_tpu.ps.aux_tables import InputTable
    cfg = DataFeedConfig(slots=(SlotConfig("s", capacity=2),
                                SlotConfig("user", dtype="string",
                                           capacity=1)))
    return DataFeed(cfg, chunk_lines=CHUNK, input_table=InputTable()), \
        "".join(f"1 {k + 1} 1 u{k % 7}\n" for k in range(40))


def _plugin_feed(spec):
    from paddlebox_tpu.data.data_feed import load_parser_plugin
    cfg = DataFeedConfig(slots=(SlotConfig("s", capacity=2),))
    feed = DataFeed(cfg, chunk_lines=CHUNK)
    feed._parser = load_parser_plugin(spec, cfg)
    return feed, "".join(f"1 {k + 1}\n" for k in range(40))


@pytest.mark.parametrize("which", ["default", "so_plugin_with_the_entry",
                                   "string_slots", "use_native_false",
                                   "python_plugin",
                                   "so_plugin_without_the_entry"])
def test_the_carrier_is_chosen_from_what_the_parser_offers(which, tmp_path):
    native_or_skip()
    from paddlebox_tpu.native import build
    cfg = DataFeedConfig(slots=(SlotConfig("s", capacity=2),))
    text = "".join(f"1 {k + 1}\n" for k in range(40))
    if which == "default":
        feed = DataFeed(cfg, chunk_lines=CHUNK)
    elif which == "use_native_false":
        feed = DataFeed(cfg, chunk_lines=CHUNK, use_native=False)
    elif which == "string_slots":
        feed, text = _string_slot_feed()
    elif which == "python_plugin":
        feed, text = _plugin_feed("tests.parser_plugin_fixture:create_parser")
    elif which == "so_plugin_with_the_entry":
        feed, text = _plugin_feed(f"{build.lib_path()}:pbox_parse_block")
    else:
        feed, text = _plugin_feed(
            _plugin_so_without_the_bytes_entry(tmp_path)
            + ":pbox_parse_block")
    path = tmp_path / "part-00000"
    path.write_text(text)
    StatRegistry.instance().reset()
    blocks = list(feed.read_file(str(path)))
    stats = stat_snapshot("data.read")
    takes_bytes = which in ("default", "so_plugin_with_the_entry")
    if takes_bytes:
        assert stats["data.read.raw_bytes"] == len(text)
        assert "data.read.text_lines" not in stats
    else:
        assert stats["data.read.text_lines"] == 40
        assert "data.read.raw_bytes" not in stats
    if which != "python_plugin":    # the fixture makes one record a chunk
        assert [b.n for b in blocks] == [16, 16, 8]
    # one sample a chunk on either carrier, and the read that found the end
    assert stats["data.read.parse_s.count"] == 3
    assert stats["data.read.lines_s.count"] == 4


# -- the block store: a pass's parsed blocks land in the memory the last
# pass's blocks held (the rebuild's SlotObjPool) ---------------------------

class ScribblingStore(BlockStore):
    """Every slab that comes back is overwritten before it can be handed
    out again: a block that still read it, or a fill that wrote short of
    its array, shows as 0xFF."""

    def give_back(self, slabs):
        slabs = list(slabs)
        for slab in slabs:
            slab[:] = 0xFF
        super().give_back(slabs)


def small_chunks(monkeypatch, buffer_bytes=DataFeed.buffer_bytes,
                 swap_parser=None):
    """Datasets made from here on read in chunks of CHUNK records through
    a ``buffer_bytes`` buffer, with ``swap_parser(feed)``'s parser if
    given."""
    from paddlebox_tpu.data import dataset as dataset_mod

    def make_feed(*a, **kw):
        feed = DataFeed(*a, chunk_lines=CHUNK, **kw)
        feed.buffer_bytes = buffer_bytes
        if swap_parser is not None:
            feed._parser = swap_parser(feed)
        return feed
    monkeypatch.setattr(dataset_mod, "DataFeed", make_feed)


def one_reader(cfg, store=None, **kw):
    ds = SlotDataset(cfg, read_threads=1, **kw)
    if store is not None:
        ds._store = store
    return ds


def load(ds, files):
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds.get_blocks()


def write_passes(tmp_path, case, other_seed=99):
    """(files of pass A: the case's file; files of pass B: another count
    of other records in the same format; DataFeed keywords)."""
    path_a, kw = write_case(case, tmp_path)
    path_b = tmp_path / "part-00001"
    path_b.write_text("\n".join(records(
        37, other_seed, ins_id=kw.get("parse_ins_id", False),
        logkey=kw.get("parse_logkey", False))) + "\n")
    return [path_a], [str(path_b)], kw


def assert_alternating_loads_equal_fresh_ones(cfg, passes, kw, loads=4):
    want = [load(one_reader(cfg, **kw), files) for files in passes]
    ds = one_reader(cfg, ScribblingStore(), **kw)
    for k in range(loads * len(passes)):
        which = k % len(passes)
        assert_blocks_equal(load(ds, passes[which]), want[which])
    return ds


@pytest.mark.parametrize("buffer_bytes", BUFFER_SIZES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_recycled_storage_holds_the_blocks_the_files_hold(
        case, buffer_bytes, tmp_path, monkeypatch):
    native_or_skip()
    small_chunks(monkeypatch, buffer_bytes)
    files_a, files_b, kw = write_passes(tmp_path, case)
    StatRegistry.instance().reset()
    assert_alternating_loads_equal_fresh_ones(
        make_config(), [files_a, files_b], kw)
    if case not in ("empty_file", "blank_lines_only"):
        assert stat_snapshot("data.read")["data.read.block_bytes_reused"] > 0


def test_recycled_storage_with_blocks_of_many_sizes(tmp_path, monkeypatch):
    """Slots of 1-40 keys, files of other lengths, passes of other file
    counts: a request finds a slab of another block's size class, or none."""
    native_or_skip()
    small_chunks(monkeypatch)
    passes = []
    for p, counts in enumerate([(70, 5, 33), (16, 90), (3,), (41, 41, 41, 2)]):
        passes.append([])
        for k, n in enumerate(counts):
            path = tmp_path / f"pass{p}-part-{k:05d}"
            path.write_text(
                "\n".join(records(n, 100 * p + k, wide=True)) + "\n")
            passes[-1].append(str(path))
    ds = assert_alternating_loads_equal_fresh_ones(make_config(), passes, {})
    sizes = {b.storage.nbytes for b in ds.get_blocks()}
    assert len(sizes) > 1


def test_reuse_share_is_zero_on_a_first_load_and_one_from_the_second(
        tmp_path, monkeypatch):
    native_or_skip()
    import json
    from paddlebox_tpu.utils import obs_server
    small_chunks(monkeypatch)
    files_a, _, _ = write_passes(tmp_path, "plain")
    StatRegistry.instance().reset()
    ds = one_reader(make_config())
    load(ds, files_a)
    first = json.loads(obs_server.render_statz(prefix="data.read"))
    assert first["data.read.block_bytes_fresh"] > 0
    assert "data.read.block_bytes_reused" not in first
    for k in range(1, 4):
        load(ds, files_a)
        statz = json.loads(obs_server.render_statz(prefix="data.read"))
        assert statz["data.read.block_bytes_fresh"] \
            == first["data.read.block_bytes_fresh"]
        assert statz["data.read.block_bytes_reused"] \
            == k * first["data.read.block_bytes_fresh"]


def test_preload_does_not_take_the_storage_of_the_live_blocks(
        tmp_path, monkeypatch):
    native_or_skip()
    small_chunks(monkeypatch)
    files_a, files_b, _ = write_passes(tmp_path, "plain")
    cfg = make_config()
    want_a, want_b = (load(one_reader(cfg), f) for f in (files_a, files_b))
    ds = one_reader(cfg, ScribblingStore())
    live = load(ds, files_a)
    slabs = {id(b.storage) for b in live}
    StatRegistry.instance().reset()
    ds.set_filelist(files_b)
    ds.preload_into_memory()
    ds._preload_future.result()         # read to its end beside the live pass
    assert "data.read.block_bytes_reused" not in stat_snapshot("data.read")
    assert_blocks_equal(live, want_a)
    ds.release_memory()                 # what end_pass does; then the swap
    ds.wait_preload_done()
    assert_blocks_equal(ds.get_blocks(), want_b)
    assert not slabs & {id(b.storage) for b in ds.get_blocks()}
    # the released pass is what the next preload draws on
    ds.set_filelist(files_a)
    ds.preload_into_memory()
    ds.wait_preload_done()
    assert stat_snapshot("data.read")["data.read.block_bytes_reused"] > 0
    assert_blocks_equal(ds.get_blocks(), want_a)


def test_store_holds_no_more_than_the_last_released_pass(tmp_path,
                                                         monkeypatch):
    native_or_skip()
    small_chunks(monkeypatch)
    large = tmp_path / "large"
    large.write_text("\n".join(records(40 * CHUNK, 1)) + "\n")
    small = tmp_path / "small"
    small.write_text("\n".join(records(3 * CHUNK, 2)) + "\n")
    ds = one_reader(make_config())

    def held():
        return sum(b.storage.nbytes for b in ds.get_blocks())

    load(ds, [str(large)])
    large_bytes = held()
    assert ds._store.free_bytes == 0
    load(ds, [str(small)])
    small_bytes = held()
    assert small_bytes < large_bytes / 8
    # what the small pass did not take of the large one's is still there
    assert ds._store.free_bytes == large_bytes - small_bytes
    ds.release_memory()
    assert ds._store.free_bytes == small_bytes
    load(ds, [str(small)])
    ds.release_memory()
    assert ds._store.free_bytes == small_bytes


def test_a_block_is_dead_once_its_dataset_replaces_it(tmp_path, monkeypatch):
    native_or_skip()
    small_chunks(monkeypatch)
    files_a, files_b, _ = write_passes(tmp_path, "plain")
    ds = one_reader(make_config())
    for replace in (lambda: load(ds, files_b), ds.release_memory,
                    ds.local_shuffle, ds.preprocess_instance):
        held = load(ds, files_a)[0]
        if replace == ds.preprocess_instance:
            for b in ds.get_blocks():       # it groups by search id
                b.search_ids = np.arange(b.n, dtype=np.uint64)
        kept = SlotRecordBlock.concat([held])   # a copy outlives it
        want = kept.all_keys().copy()
        replace()
        assert held.storage is None
        for read in (held.all_keys, lambda: held.uint64_slots["slot_a"],
                     lambda: held.feasign_count,
                     lambda: SlotRecordBlock.concat([held])):
            with pytest.raises(RuntimeError, match="valid until"):
                read()
        load(ds, files_b)
        assert np.array_equal(kept.all_keys(), want)


def test_sample_rate_gives_each_parsed_block_back_as_it_is_dropped(
        tmp_path, monkeypatch):
    native_or_skip()
    import dataclasses
    small_chunks(monkeypatch)
    path = tmp_path / "part-00000"
    path.write_text("\n".join(records(20 * CHUNK, 3)) + "\n")
    cfg = dataclasses.replace(make_config(), sample_rate=0.5, rand_seed=7)
    want = load(one_reader(cfg), [str(path)])
    StatRegistry.instance().reset()
    ds = one_reader(cfg, ScribblingStore())
    assert_blocks_equal(load(ds, [str(path)]), want)
    assert all(b.storage is None for b in ds.get_blocks())
    stats = stat_snapshot("data.read")
    # one slab serves the file: each chunk is parsed where the last lay
    assert stats["data.read.block_bytes_reused"] \
        > 10 * stats["data.read.block_bytes_fresh"]


@pytest.mark.parametrize("which", ["use_native_false", "lines_only",
                                   "python_plugin", "string_slots",
                                   "so_plugin_without_the_entry"])
def test_the_text_path_keeps_allocating_and_reads_the_same(which, tmp_path,
                                                           monkeypatch):
    native_or_skip()
    from paddlebox_tpu.data.data_feed import load_parser_plugin, make_parser
    cfg, kw = make_config(), {}
    texts = ["\n".join(records(n, s)) + "\n" for n, s in ((50, 0), (37, 1))]
    if which == "use_native_false":
        swap = lambda feed: make_parser(feed.config, use_native=False)
    elif which == "lines_only":
        swap = lambda feed: LinesOnly(feed._parser)
    elif which == "string_slots":
        swap = None
        cfg = _string_slot_feed()[0].config
        texts = ["".join(f"1 {k + 1} 1 u{k % m}\n" for k in range(40))
                 for m in (7, 5)]
    else:
        cfg = DataFeedConfig(slots=(SlotConfig("s", capacity=2),))
        texts = ["".join(f"1 {k + s}\n" for k in range(40)) for s in (1, 9)]
        spec = "tests.parser_plugin_fixture:create_parser" \
            if which == "python_plugin" else \
            _plugin_so_without_the_bytes_entry(tmp_path) + ":pbox_parse_block"
        swap = lambda feed: load_parser_plugin(spec, feed.config)
    small_chunks(monkeypatch, swap_parser=swap)
    passes = []
    for k, text in enumerate(texts):
        path = tmp_path / f"part-{k:05d}"
        path.write_text(text)
        passes.append([str(path)])
    StatRegistry.instance().reset()
    ds = assert_alternating_loads_equal_fresh_ones(cfg, passes, kw)
    assert all(b.storage is None for b in ds.get_blocks())
    stats = stat_snapshot("data.read")
    assert stats["data.read.text_lines"] > 0
    assert not [k for k in stats if "block_bytes" in k]
