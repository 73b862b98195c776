"""The two Mosaic kernels of ``ops/sorted_spmm.py`` in a trace: their
device time a step, and their share of the roofline."""

from __future__ import annotations

from typing import Optional

from benchmark.harness import opsbytes, xplane

GATHER = "sorted_spmm_gather"
SCATTER = "sorted_spmm_scatter"
NAMES = (GATHER, SCATTER)


def seconds_per_step(run, name: str) -> Optional[float]:
    """Device seconds of the kernel a step, mean over steps and chips."""
    if run.trace is None or run.trace_window is None:
        return None
    per_chip = []
    for plane in xplane.device_planes(run.trace)[:run.chips]:
        steps = len(run.step_runs(plane))
        events = xplane.matching(
            xplane.ops(run.trace, plane, run.trace_window), name)
        if not steps or not events:
            return None
        per_chip.append(sum(e.end - e.start for e in events) / 1e9 / steps)
    return sum(per_chip) / len(per_chip) if per_chip else None


def job(run, name: str) -> dict:
    """What one call of the kernel has to do on one chip, from shapes."""
    g = run.geometry
    if name == GATHER:
        return opsbytes.gather(g["occurrences_kept"], g["gather_width"])
    return opsbytes.scatter_add(g["occurrences_kept"], g["scatter_width"],
                                g["table_rows_per_device"])


def roofline_percent(run, name: str) -> Optional[float]:
    took = seconds_per_step(run, name)
    if took is None or run.peaks is None:
        return None
    return 100.0 * opsbytes.least_seconds(job(run, name),
                                          run.peaks)["seconds"] / took
