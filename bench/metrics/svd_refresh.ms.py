"""One SVD projection refresh, device and gaps, in ms: the interval between
the train steps around the refresh less the median interval of the others."""
from bench import readers


def read(run):
    refresh, plain = readers.step_pairs(run)
    if not refresh or not plain:
        return None
    typical = readers.median_or_none(b.start - a.start for a, b in plain)
    a, b = refresh[0]
    return (b.start - a.start - typical) * 1e3
