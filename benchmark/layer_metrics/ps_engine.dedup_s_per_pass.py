"""Layer ``ps_engine`` (ps/pass_manager.py): seconds a pass spends
concatenating and deduplicating its keys (``np.unique``), from the
program's span ``ps.engine.dedup_keys`` over the window."""

from benchmark.harness import program_spans


def read(run):
    return program_spans.per_pass(run, "ps.engine.dedup_keys")
