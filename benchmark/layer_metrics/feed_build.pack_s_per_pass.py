"""Layer ``feed_build`` (data/pass_feed.py through
``trainer.pack_pass_host``): seconds a pass spends packing its planes on
the host (on the prefetch worker when the feed is pipelined), from the
program's span ``trainer.pack_pass_host`` over the window."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "trainer.pack_pass_host")
