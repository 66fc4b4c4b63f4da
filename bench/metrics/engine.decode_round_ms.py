"""Mean of the ``engine.decode_round`` span (dispatch, device, read back)
in ms."""
from bench import readers


def read(run):
    n, s = readers.span(run, "router.step/engine.decode_round")
    return s / n * 1e3 if n else None
