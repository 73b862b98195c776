"""Layer ``read_parse`` (data/dataset.py, data/data_feed.py,
native/slot_parser.cc): seconds a pass spends in ``load_into_memory``
(read, parse, key tap), mean over the window's passes.  The benchmark's
span, host clock; on the prefetch worker when the feed is pipelined."""

import statistics


def read(run):
    spans = run.span_seconds("load_into_memory")
    return statistics.fmean(spans) if spans else None
