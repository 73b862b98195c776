"""Layer ``tower`` (models/looplm.py): the share of the positions a step
computes that lie beyond their sequence's length (padded, computed and
masked), from the program's counters ``tower.tokens_padded`` and
``tower.tokens_valid`` over the window."""


def read(run):
    padded = run.stats.get("tower.tokens_padded")
    valid = run.stats.get("tower.tokens_valid")
    if padded is None or valid is None or padded + valid <= 0:
        return None
    return 100.0 * padded / (padded + valid)
