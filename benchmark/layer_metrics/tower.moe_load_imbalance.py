"""Layer ``tower`` (models/hybridlm.py, parallel/moe.py): how unevenly the
router loads the experts this chip holds: tokens the busiest (routed
layer, held expert) received over a pass / the mean over all of them,
from the program's counters ``tower.moe.expert_load_max`` and
``tower.moe.expert_load_mean`` as the generator kept them over the whole
run, warm-up and compared epoch included (``geometry.moe_run_load_*``:
the check ``moe_dropped`` says the held experts received some there;
with no balancing bias run a window alone may route nothing to this
chip, PERF.md section 6).  1 is an even load.  No load, or a program
without the counters, leaves the metric out."""


def read(run):
    top = run.geometry.get("moe_run_load_max")
    mean = run.geometry.get("moe_run_load_mean")
    if top is None or not mean:
        return None
    return top / mean
