"""Share of the traced stretch in which no operation ran on the chip, in %."""


def read(run):
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
