"""Layer ``read_parse`` (native/slot_parser.cc through data/data_feed.py):
thread-seconds a pass spends in ``parse_block``, from the program's span
``data.read.parse`` over the window, summed over the reader threads."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "data.read.parse")
