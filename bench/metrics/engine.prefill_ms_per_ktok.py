"""Device time of the prefill programs (forward and scatter into pages) per
1,000 prompt tokens prefilled in the stretch, in ms."""
from bench import readers


def read(run):
    toks = sum(sum(pre) for _, pre in run.info.get("lanes", []))
    dev = sum(e.dur for e in readers.programs_named(run, "prefill"))
    if not toks or not dev:
        return None
    return dev * 1e3 / (toks / 1000.0)
