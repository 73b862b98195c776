"""Layer ``step_build`` (trainer._build_packed_step, ops/crossing.py):
XLA compile requests inside the window, as JAX's own monitoring events
count them (a persistent-cache hit is still a request).  The target
is 0."""


def read(run):
    return len(run.compiles)
