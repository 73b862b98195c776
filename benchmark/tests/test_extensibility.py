"""A later PR adds a cell, a configuration, a traffic mix, a generator
kind and a layer metric by adding files and entries, and edits no file
that is there.  Shown on a temporary copy: the new files are written, the
copy's BENCHMARK.json gains entries, every file that was there keeps its
bytes, and the harness runs the new cells under ``--rehearse``:

* ``toy_tower.twice``: a new configuration under a new kind of traffic,
  read by a new layer metric;
* ``toy_tower.stream``: the new configuration under a mix that is there;
* ``widedeep_seq.epochs_x4``: a configuration and a mix that are both
  there, paired for the first time, on four (virtual) chips, with the
  ``exchange`` readers that wait for such a cell.
"""

import hashlib
import json
import os
import shutil

import pytest

from conftest import ROOT, run_cell

PER_LAYER = {"better": "lower", "moves": "examples_per_s"}


def digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        if "_work" in base or "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The temporary copy with the later PR's files and entries, and the
    digest of what was there before.  (A few hundred tiny examples teach
    a tower nothing, so the cells' AUC floors only ask for a number.)"""
    root = str(tmp_path_factory.mktemp("later_pr"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("_work", "__pycache__"))
    before = digest(root)
    bench = os.path.join(root, "benchmark")

    # a configuration: DeepFM's file with another tower, its model glue
    # and its reference beside it
    with open(os.path.join(bench, "configs", "deepfm_criteo.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "toy_tower"
    cfg["rehearsal"]["model"] = {"hidden": [16]}
    write_json(os.path.join(bench, "configs", "toy_tower.json"), cfg)
    for kind in ("models", "reference"):
        shutil.copy(os.path.join(bench, kind, "deepfm_criteo.py"),
                    os.path.join(bench, kind, "toy_tower.py"))
    # a generator kind and a mix that names it
    with open(os.path.join(bench, "generators", "twice.py"), "w") as f:
        f.write("from benchmark.generators import epochs\n\n\n"
                "def run(ctx):\n"
                "    measured = epochs.run(ctx)\n"
                "    measured.checks['twice_ran'] = {'ok': True}\n"
                "    return measured\n")
    write_json(os.path.join(bench, "traffic", "twice.json"),
               {"kind": "twice", "files_per_pass": 1, "warmup_epochs": 1,
                "trace_seconds": 1.0})
    # a layer metric: a reader of its own
    with open(os.path.join(bench, "layer_metrics", "toy.epochs_done.py"),
              "w") as f:
        f.write("def read(run):\n    return len(run.units)\n")
    # each cell's own parameters
    write_json(os.path.join(bench, "cells", "toy_tower.twice.json"),
               {"depth": 3, "auc_floor": 0.4})
    write_json(os.path.join(bench, "cells", "toy_tower.stream.json"),
               {"depth": 2, "passes": 3, "auc_floor": 0.4})
    write_json(os.path.join(bench, "cells", "widedeep_seq.epochs_x4.json"),
               {"depth": 2, "auc_floor": 0.4})

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy_tower", "source": cfg["source"],
                            "file": "benchmark/configs/toy_tower.json",
                            "reduced": cfg["reduced"], "why": "self-test"})
    spec["workloads"] += [
        {"name": "toy_tower.twice", "config": "toy_tower",
         "traffic": "twice", "chips": 1, "why": "self-test"},
        {"name": "toy_tower.stream", "config": "toy_tower",
         "traffic": "stream", "chips": 1, "why": "self-test"},
        {"name": "widedeep_seq.epochs_x4", "config": "widedeep_seq",
         "traffic": "epochs_x4", "chips": 4, "why": "self-test"}]
    spec["per_layer"] += [
        {"name": "toy.epochs_done", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "step",
         "moves": "examples_per_s", "workloads": ["toy_tower.twice"]},
        {"name": "exchange.collective_ms", "unit": "ms", "layer": "exchange",
         "source": "device_trace", **PER_LAYER,
         "workloads": ["widedeep_seq.epochs_x4"]},
        {"name": "exchange.exposed_share", "unit": "%", "layer": "exchange",
         "source": "device_trace", **PER_LAYER,
         "workloads": ["widedeep_seq.epochs_x4"]}]
    for m in spec["end_to_end"]:
        if m["name"] == "pass_turnaround_s":
            m["workloads"].append("toy_tower.stream")
    write_json(os.path.join(root, "BENCHMARK.json"), spec)
    return root, before


def rehearse(root, cell, trace):
    rc, result, err = run_cell(
        ["--workload", cell, "--seed", "3", "--seconds", "1", "--trace",
         trace, "--rehearse"], root=root,
        env={"PYTHONPATH": ROOT})       # the program; the copy has none
    assert rc == 0, err[-2000:]
    return result


def test_a_configuration_a_kind_a_mix_and_a_metric(grown):
    root, _ = grown
    for trace in ("0", "1"):
        result = rehearse(root, "toy_tower.twice", trace)
        assert result["correct"] is True
        assert result["detail"]["checks"]["twice_ran"]["ok"]
    assert result["metrics"]["toy.epochs_done"]["value"] >= 1


def test_a_new_configuration_on_a_mix_that_is_there(grown):
    root, _ = grown
    result = rehearse(root, "toy_tower.stream", "0")
    assert result["correct"] is True, result["detail"]["checks"]
    assert result["attempted"] == 3 and result["detail"]["units"] == 3
    assert set(result["metrics"]) == {"examples_per_s", "pass_turnaround_s",
                                      "setup_s"}


def test_a_four_chip_cell_of_what_is_there(grown):
    root, _ = grown
    result = rehearse(root, "widedeep_seq.epochs_x4", "1")
    assert result["correct"] is True, result["detail"]["checks"]
    assert result["device"]["count"] == 4
    assert result["detail"]["lowering"] == "mxu_sharded"
    # no device plane on the CPU: the exchange readers find nothing and
    # the metrics are left out, the counters are there
    assert "exchange.collective_ms" not in result["metrics"]
    assert result["metrics"]["step_build.compiles_in_window"]["value"] == 0


def test_no_file_that_was_there_changed(grown):
    root, before = grown
    # an old cell still runs from the copy, and does not report the new
    # metric
    result = rehearse(root, "deepfm_criteo.epochs", "1")
    assert "toy.epochs_done" not in result["metrics"]
    after = digest(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/cells/toy_tower.stream.json",
        "benchmark/cells/toy_tower.twice.json",
        "benchmark/cells/widedeep_seq.epochs_x4.json",
        "benchmark/configs/toy_tower.json",
        "benchmark/generators/twice.py",
        "benchmark/layer_metrics/toy.epochs_done.py",
        "benchmark/models/toy_tower.py",
        "benchmark/reference/toy_tower.py",
        "benchmark/traffic/twice.json"]
