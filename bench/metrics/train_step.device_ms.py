"""Mean device time of the jitted train step, in ms."""
from bench import readers


def read(run):
    v = readers.mean_or_none(e.dur for e in readers.train_steps(run))
    return None if v is None else v * 1e3
