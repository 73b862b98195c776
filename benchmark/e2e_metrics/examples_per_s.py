"""Examples trained per second per chip: whole passes (or whole epochs)
finished in the window, over the window's measured length and the cell's
chips.  Host clock; every pass or epoch ends in a device read-back."""


def read(run):
    if not run.units:
        return None
    return sum(u.examples for u in run.units) / run.elapsed_s / run.chips
