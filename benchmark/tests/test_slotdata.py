"""The pass-file generator: it reproduces from the seed, writes what it
drew (checked against a loop that formats line by line, and by parsing the
file back with the program's parsers), and reports the key and length
statistics of a pass."""

import os

import numpy as np
import pytest

from benchmark.harness import slotdata, spec


def config(name, rehearsal=False):
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      name + ".json"))
    if rehearsal:
        cfg = dict(cfg, fields={**cfg["fields"],
                                **cfg["rehearsal"]["fields"]})
    return cfg


def format_by_loop(fields, ex):
    """The same file, one line at a time."""
    lines, pos = [], 0
    w = fields.key_digits
    for i in range(ex["lens"].shape[0]):
        dense = " ".join("%d.%04d" % divmod(int(v), slotdata.DENSE_SCALE)
                         for v in ex["dense"][i])
        head = f"1 {ex['labels'][i]} {fields.dense_dim} {dense} "
        head += " " * (-len(head) % slotdata.HEAD_PAD)
        toks = []
        for n in ex["lens"][i]:
            keys = ex["keys"][pos:pos + n]
            pos += n
            toks.append("%02d " % n + "    ".join(
                "%0*d" % (w, k) for k in keys) + " ")
        lines.append((head + "".join(toks))[:-1] + "\n")
    return "".join(lines).encode()


@pytest.mark.parametrize("name", ["deepfm_criteo", "widedeep_seq"])
def test_bytes_match_the_loop_and_reproduce(name):
    fields = slotdata.Fields(config(name))
    ex = slotdata.draw_file(fields, 300, seed=3, pass_id=0, file_id=1)
    got = slotdata.file_bytes(fields, ex).tobytes()
    assert got == format_by_loop(fields, ex)
    again = slotdata.draw_file(fields, 300, seed=3, pass_id=0, file_id=1)
    assert slotdata.file_bytes(fields, again).tobytes() == got
    other = slotdata.draw_file(fields, 300, seed=4, pass_id=0, file_id=1)
    assert slotdata.file_bytes(fields, other).tobytes() != got


@pytest.mark.parametrize("name", ["deepfm_criteo", "widedeep_seq"])
@pytest.mark.parametrize("native", [False, True])
def test_the_programs_parsers_read_back_what_was_drawn(name, native,
                                                       tmp_path):
    from paddlebox_tpu.data.data_feed import DataFeed
    from benchmark.harness.program import feed_config
    cfg = config(name)
    fields = slotdata.Fields(cfg)
    ex = slotdata.draw_file(fields, 500, seed=9, pass_id=1, file_id=0)
    path = tmp_path / "part.txt"
    slotdata.file_bytes(fields, ex).tofile(path)
    feed = DataFeed(feed_config(cfg, 64), use_native=native)
    blocks = list(feed.read_file(str(path)))
    assert sum(b.n for b in blocks) == 500
    block = blocks[0]
    keys, lens = [], []
    for s in range(fields.n_slots):
        values, offsets = block.uint64_slots[f"s{s}"]
        lens.append(np.diff(offsets))
        keys.append((values, offsets))
    lens = np.stack(lens, axis=1)
    assert np.array_equal(lens, ex["lens"])
    flat = np.concatenate([keys[s][0][keys[s][1][i]:keys[s][1][i + 1]]
                           for i in range(500)
                           for s in range(fields.n_slots)])
    assert np.array_equal(flat, ex["keys"].astype(np.uint64))
    assert np.allclose(block.float_slots["dense0"][0].reshape(500, -1),
                       ex["dense"] / slotdata.DENSE_SCALE, atol=1e-6)
    assert np.array_equal(block.float_slots["label"][0], ex["labels"])


def test_pass_statistics_and_probe_counts(tmp_path):
    """What PERF.md quotes of a pass: uniques a pass, occurrences an
    example, the longest slot; and the probe keys' own counts."""
    cfg = config("widedeep_seq")
    fields = slotdata.Fields(cfg)
    metas = slotdata.write_passes(str(tmp_path), fields, seed=5, n_passes=2,
                                  n_examples=4096, n_files=3)
    st = metas[0]["stats"]
    keys = np.concatenate([
        slotdata.draw_file(fields, n, 5, 0, i)["keys"]
        for i, n in enumerate([1366, 1366, 1364])])
    assert st["examples"] == 4096 and st["occurrences"] == keys.size
    assert st["unique_keys"] == np.unique(keys).size
    # twenty single-valued slots and six of log-normal length (median 4,
    # sigma 1, cut to 1..16): about 54 keys an example
    assert 50 < st["occurrences_per_example"] < 58
    assert st["max_slot_len"] == cfg["lengths"]["max"]
    probe = metas[0]["probe"]
    assert len(probe["keys"]) == 64 + slotdata.N_HOT
    assert probe["counts"] == [int((keys == k).sum())
                               for k in probe["keys"]]
    # the second pass counts the same keys, and a pass trained twice
    # counts twice
    assert metas[1]["probe"]["keys"] == probe["keys"]
    total = slotdata.probe_counts(metas, [0, 1, 0])
    k = probe["keys"][0]
    assert total[k] == 2 * probe["counts"][0] + metas[1]["probe"]["counts"][0]
    # the same seed writes the same pass
    again = slotdata.write_passes(str(tmp_path / "again"), fields, seed=5,
                                  n_passes=1, n_examples=4096, n_files=3)
    assert again[0]["probe"] == probe and again[0]["stats"] == st


def test_deepfm_has_one_key_a_slot_within_its_field():
    cfg = config("deepfm_criteo")
    fields = slotdata.Fields(cfg)
    ex = slotdata.draw_file(fields, 2000, seed=1, pass_id=0, file_id=0)
    assert (ex["lens"] == 1).all()
    keys = ex["keys"].reshape(2000, fields.n_slots)
    lo = 1 + fields.offsets
    assert (keys >= lo).all() and (keys < lo + fields.vocab).all()
    assert int(fields.vocab.sum()) == 33_762_577


def test_keys_are_zipf_and_spread_over_the_field():
    """The head is heavy (the top rank's share is what the power law
    says) and a field's hot keys do not sit at the low end of its
    range."""
    vocab = np.full(200_000, 1_000_000, np.int64)
    u = np.random.default_rng(0).random(200_000)
    ranks = slotdata.zipf_ranks(u, vocab, 1.1)
    a = -0.1
    top_share = (2.0 ** a - 1.0) / ((1_000_001.0) ** a - 1.0)
    assert abs((ranks == 0).mean() - top_share) < 0.01
    assert ranks.max() < 1_000_000 and ranks.min() == 0
    fields = slotdata.Fields(config("deepfm_criteo"))
    hot = fields.keys_of(np.full(100, 2), np.arange(100))
    assert len(set(hot.tolist())) == 100
    assert hot.max() - hot.min() > fields.vocab[2] // 2


def test_labels_follow_the_hidden_weights():
    """The label model is learnable: the examples the hidden weights
    favour click more."""
    cfg = config("deepfm_criteo")
    fields = slotdata.Fields(cfg)
    ex = slotdata.draw_file(fields, 20000, seed=2, pass_id=0, file_id=0)
    keys = ex["keys"].reshape(20000, fields.n_slots)
    score = np.zeros(20000)
    for slot, w in slotdata.hidden_weights(fields, 2).items():
        score += w[keys[:, slot] - 1 - fields.offsets[slot]]
    top, bottom = score > np.quantile(score, .8), score < np.quantile(score, .2)
    assert ex["labels"][top].mean() > ex["labels"][bottom].mean() + 0.2
    assert 0.15 < ex["labels"].mean() < 0.45
