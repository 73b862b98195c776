"""Operations and bytes the two sorted-SpMM kernels' jobs need, from
shapes: the numerators of their roofline shares.

What is counted is the job, not the implementation.  The kernels do the
job as one-hot matmuls on the MXU (two bf16 passes over a 2048-row tile a
work item), which costs far more arithmetic than the job asks for; a
roofline share says how far the kernel is from the least the chip could
do, so the one-hot arithmetic is overhead, not work.

* gather: ``p`` occurrences each read one table row of ``w`` float32 and
  write it to the sorted domain: ``p`` row ids in, ``p*w`` values read,
  ``p*w`` written, no arithmetic.
* scatter-add: ``p`` payload rows of ``w`` float32 are read with their
  ids and summed into a zero-filled ``[w, rows]`` delta that is written
  once: ``p*w`` adds.

Both are bound by memory bandwidth on every chip in ``peaks.json``: the
gather does no arithmetic and the scatter one add per four bytes read.
"""

from __future__ import annotations


def gather(p: int, w: int) -> dict:
    return {"ops": 0, "bytes": 4 * p + 2 * 4 * p * w}


def scatter_add(p: int, w: int, rows: int) -> dict:
    return {"ops": p * w, "bytes": 4 * p + 4 * p * w + 4 * rows * w}


def least_seconds(job: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which roof sets it."""
    by_compute = job["ops"] / peaks["f32_flops"]
    by_memory = job["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_compute, by_memory),
            "bound": "memory" if by_memory >= by_compute else "compute"}
