"""Every cell end to end at its rehearsal sizes on the CPU: the program's
step against the plain reference (both configurations; four virtual
devices are ``test_extensibility``'s), the write-back against the
generator's own counts, and the shape of the result line."""

import math

import pytest

from benchmark.harness import checks, spec
from conftest import run_cell

CELLS = [w["name"] for w in spec.load_json(
    spec.ROOT + "/BENCHMARK.json")["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module", params=CELLS)
def rehearsed(request):
    rc, result, err = run_cell(["--workload", request.param, "--seed", "5",
                                "--seconds", "1", "--trace", "0",
                                "--rehearse"])
    assert rc == 0, err[-2000:]
    return request.param, result


def test_reference_agrees_with_the_programs_step(rehearsed):
    _, result = rehearsed
    check = result["detail"]["checks"]["reference_losses"]
    assert check["ok"] and len(check["reference"]) >= 2
    # on the CPU nothing but summation order and the kernels' hi/lo split
    # separates the two: far inside the on-chip gate
    for got, want in zip(check["program"], check["reference"]):
        assert abs(got - want) <= 1e-4 * abs(want)


def test_cell_is_correct_and_wrote_back(rehearsed):
    name, result = rehearsed
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    back = result["detail"]["checks"]["write_back"]
    assert back["ok"] and back["keys"] > 0 and back["show_mismatches"] == 0
    chips = spec.Cell(name).chips
    assert result["device"]["count"] == chips
    assert result["detail"]["lowering"] == (
        "mxu_sharded" if chips == 4 else "mxu")


def test_no_cpu_number_under_a_metrics_name(rehearsed):
    name, result = rehearsed
    cell = spec.Cell(name)
    want = {m["name"] for m in cell.metrics("end_to_end")}
    assert set(result["metrics"]) == want
    assert all(m["value"] is None for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"


def test_traced_rehearsal_reports_counts_only():
    rc, result, err = run_cell(["--workload", "deepfm_criteo.stream",
                                "--seed", "6", "--seconds", "1",
                                "--trace", "1", "--rehearse"])
    assert rc == 0, err[-2000:]
    metrics = result["metrics"]
    assert metrics["step_build.compiles_in_window"]["value"] == 0
    timed = [k for k, m in metrics.items() if m["unit"] != "count"]
    assert timed and all(metrics[k]["value"] is None for k in timed)
    # no device plane on the CPU: the trace-derived readers return nothing
    assert "step.device_ms" not in metrics
    assert result["device"]["window_s"] > 0


def test_bf16_operand_matmul_rounds_forward_and_backward():
    """``reference_matmul`` = ``bf16_operands``: both operands of the
    product and of the two backward products are rounded to bfloat16,
    sums stay float32; ``float32`` is the plain product."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import step as reference
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(5, 7)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(7, 3)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)

    def r(x):
        return np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32),
                          np.float64)

    mm = reference.matmul("bf16_operands")
    out, vjp = jax.vjp(mm, a, b)
    da, db = vjp(g)
    np.testing.assert_allclose(out, r(a) @ r(b), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(da, r(g) @ r(b).T, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(db, r(a).T @ r(g), rtol=1e-6, atol=1e-6)
    # the rounding is there: bfloat16 keeps 8 bits, so 1e-3 to 1e-2 off
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    assert 1e-4 < np.abs(np.asarray(out) - exact).max() < 1e-1
    plain = reference.matmul("float32")(a, b)
    np.testing.assert_allclose(plain, exact, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        reference.matmul("float16")


def test_the_gate_tells_a_wrong_step_apart():
    ref = checks.ReferenceCheck.__new__(checks.ReferenceCheck)
    ref.losses, ref.rtol, ref.matmul = [0.7, 0.69, 0.68], 5e-3, "float32"
    assert ref.compare([0.7001, 0.6901, 0.6799])["ok"]
    assert not ref.compare([0.7, 0.69, 0.68 * 1.01])["ok"]
    assert not ref.compare([0.7, math.nan, 0.68])["ok"]
    assert not ref.compare([0.7])["ok"]
