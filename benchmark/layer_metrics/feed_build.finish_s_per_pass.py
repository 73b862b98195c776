"""Layer ``feed_build`` (``trainer.finish_pass_feed``): seconds a pass
spends enqueueing its upload and building its plans, from the program's
span ``trainer.finish_pass_feed`` over the window.  On the training
thread, so never hidden behind a training pass."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "trainer.finish_pass_feed")
