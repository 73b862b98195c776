"""Layer ``read_parse`` (data/dataset.py): thread-seconds a pass spends
handing a block's keys to the engine (``all_keys()``, the key consumers,
the append under the lock), from the program's span ``data.read.key_tap``
over the window, summed over the reader threads."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "data.read.key_tap")
