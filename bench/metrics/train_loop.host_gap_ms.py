"""Mean device-idle time between consecutive train-step programs (pairs
around an SVD refresh left out), in ms: the host's share of a step."""
from bench import readers


def read(run):
    _, plain = readers.step_pairs(run)
    v = readers.mean_or_none(readers.idle_between(run, a.end, b.start)
                             for a, b in plain)
    return None if v is None else v * 1e3
