"""Host time of one training iteration outside the blocking loss read, in
ms: the ``trainer.step`` span less its ``trainer.read`` child, per step.
The program spans hold the feed, the NDB masks, the dispatch, the chaos
control plane and the bookkeeping; a program without the read span gives
nothing."""
from bench import readers


def read(run):
    n, total = readers.span(run, "trainer.step")
    _, wait = readers.span(run, "trainer.step/trainer.read")
    if not n or not wait:
        return None
    return (total - wait) / n * 1e3
