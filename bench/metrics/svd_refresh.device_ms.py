"""Device time of one SVD projection refresh, in ms: the
``svd_projection`` programs (one per weight shape) of the stretch over
the number of refreshes in it."""
from bench import readers


def read(run):
    progs = readers.programs_named(run, "svd_projection")
    refresh, _ = readers.step_pairs(run)
    if not progs or not refresh:
        return None
    return sum(e.dur for e in progs) / len(refresh) * 1e3
