"""Host time of one router step outside the engine spans it encloses, in
ms: the ``router.step`` span less its direct ``engine.*`` children."""
from bench import readers


def read(run):
    n, total = readers.span(run, "router.step")
    if not n:
        return None
    inner = sum(s for p, (c, s) in run.spans.items()
                if p.startswith("router.step/engine.") and p.count("/") == 1)
    return (total - inner) / n * 1e3
