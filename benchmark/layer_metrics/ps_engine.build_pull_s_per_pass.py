"""Layer ``ps_engine`` (ps/pass_manager.py, ps/host_table.py): seconds a
pass spends pulling its rows from the host table, from the program's own
counter ``ps.engine.build_pull_s`` over the window, a pass."""


def read(run):
    passes = run.stats.get("data.prefetch.passes") or len(run.units)
    took = run.stats.get("ps.engine.build_pull_s")
    return took / passes if took and passes else None
