"""Layer ``read_parse`` (data/data_feed.py): thread-seconds a pass spends
in the Python loop that reads and strips a chunk's lines, from the
program's span ``data.read.lines`` over the window.  Summed over the
reader threads, so it may exceed ``read_parse.s_per_pass``; the loop holds
the GIL, so the threads' seconds need not divide the wall evenly."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "data.read.lines")
