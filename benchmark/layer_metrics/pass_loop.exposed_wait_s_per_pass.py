"""Layer ``pass_loop`` (data/prefetch.py): seconds a pass the training
thread sat in ``next_pass`` waiting for the prefetch worker, from the
program's span ``data.prefetch.wait`` over the window: feed time the
pipeline did not hide."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "data.prefetch.wait")
