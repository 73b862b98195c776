"""Median, over the window's passes, of the seconds from a pass's
filelist being handed to the loop (its ``load_into_memory`` starts) to
its ``end_pass`` write-back returning: the freshness a retraining team
waits for.  Host clock."""

import statistics


def read(run):
    if not run.units:
        return None
    return statistics.median(u.t1 - u.t0 for u in run.units)
