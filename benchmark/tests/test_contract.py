"""BENCHMARK.json against the contract's limits, each entry against the
file it names, and the refusals: no chip, no program."""

import os
import re
import shutil

from benchmark.harness import spec
from conftest import ROOT, run_cell

B = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][:2] == ["python3", "benchmark/run.py"]
    assert B["paths"] == ["benchmark"]
    assert 1 <= B["run_seconds"] <= 51
    assert 2 <= len(B["workloads"]) <= 24 and 1 <= len(B["configs"]) <= 24
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[g]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        own = [x["name"] for x in B[group]]
        assert len(own) == len(set(own))
    assert all(len(x["why"]) <= 200 for x in B["configs"] + B["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 4)
    assert {w["config"] for w in B["workloads"]} == \
        {c["name"] for c in B["configs"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_metrics():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
        assert m["better"] in {"higher", "lower"}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert "bound" not in m and m["source"] in SOURCES
        assert m["moves"] in e2e
        # a per-layer metric is reported only where the metric it moves is
        where = set(m.get("workloads", cells))
        assert where <= set(e2e[m["moves"]].get("workloads", cells))
    for w in B["workloads"]:
        cell = spec.Cell(w["name"])
        assert len(cell.metrics("end_to_end")) >= 2
        assert len(cell.metrics("per_layer")) >= 1
    roofs = [m for m in B["per_layer"] if m["name"].endswith("_roofline")]
    assert roofs and all(m["unit"] == "%" for m in roofs)


def keys_of(tree):
    """Every key of a nested JSON object."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield k
            yield from keys_of(v)


def test_every_entry_has_its_files():
    for c in B["configs"]:
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("benchmark/") and cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert {"assumed", "flags", "rehearsal"} <= set(cfg)
        assert set(cfg["reduced"]) <= set(cfg)
        # what belongs to a pair is in the pair's file: no mix is named
        # in a configuration, no configuration in a mix
        mixes = {os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(spec.BENCH_DIR, "traffic"))}
        assert not mixes & set(keys_of(cfg))
        for mix in mixes:
            assert c["name"] not in set(keys_of(spec.load_json(os.path.join(
                spec.BENCH_DIR, "traffic", mix + ".json"))))
        for kind in ("models", "reference"):
            assert os.path.exists(os.path.join(spec.BENCH_DIR, kind,
                                               c["name"] + ".py"))
    for w in B["workloads"]:
        cell = spec.Cell(w["name"])
        # the pair's own file: depth and AUC floor at both sizes, and a
        # stream cell's count of passes
        for rehearse in (False, True):
            assert int(cell.param("depth", rehearse)) > 0
            # on the chip a floor that only learning reaches
            assert (0.0 if rehearse else 0.6) <= float(
                cell.param("auc_floor", rehearse)) < 1
            if cell.traffic["kind"] == "stream":
                assert int(cell.param("passes", rehearse)) >= 1
        assert cell.config["correct"]["reference_matmul"] in (
            "float32", "device_default")
        cell.module("generators", cell.traffic["kind"]).run
        for group, kind in (("end_to_end", "e2e_metrics"),
                            ("per_layer", "layer_metrics")):
            for m in cell.metrics(group):
                assert callable(cell.module(kind, m["name"]).read)


def test_no_chip_is_an_error_not_the_cpu():
    rc, result, err = run_cell(["--workload", "deepfm_criteo.epochs",
                                "--seed", "1", "--seconds", "1",
                                "--trace", "0"])
    assert rc != 0 and result is None
    assert "not a TPU" in err


def test_no_program_is_an_error(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is nothing to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("_work", "__pycache__"))
    rc, result, err = run_cell(["--workload", "deepfm_criteo.epochs",
                                "--seed", "1", "--seconds", "1",
                                "--trace", "0", "--rehearse"],
                               root=str(tmp_path),
                               env={"PYTHONPATH": ""})
    assert rc != 0 and result is None
    assert "the program is not here" in err
