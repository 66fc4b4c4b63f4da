"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader's ``read(run)`` gets the traced run: ``run.trace`` (the
reduction of the device trace over the traced stretch, see
``trace_reduce.Reduction``), ``run.spans`` (the program's span totals over
the stretch), ``run.info`` (what the driver counted there), ``run.model``
(sizes) and ``run.peaks``.  It returns a number, or ``None`` where the
stretch holds nothing it reads.
"""
from __future__ import annotations

from statistics import median
from typing import List, Optional, Tuple

from bench.trace_reduce import Event


def programs_named(run, *parts: str) -> List[Event]:
    return [e for e in run.trace.module_events
            if any(p in e.name for p in parts)]


def train_step_program(run) -> Optional[str]:
    """The program that runs once a step and takes most device time among
    those in a training stretch."""
    n = run.info.get("traced_steps", 0)
    progs = {k: v for k, v in run.trace.programs.items() if v[0] >= n}
    if not n or not progs:
        return None
    return max(progs, key=lambda k: progs[k][1])


def train_steps(run) -> List[Event]:
    name = train_step_program(run)
    if name is None:
        return []
    return [e for e in run.trace.module_events if e.name.split("(")[0] == name]


def step_pairs(run) -> Tuple[List[Tuple[Event, Event]], List[Tuple[Event, Event]]]:
    """Consecutive train-step programs, split into the pairs around an SVD
    refresh and the others."""
    steps = train_steps(run)
    svd = [e.start for e in programs_named(run, "svd")]
    refresh, plain = [], []
    for a, b in zip(steps, steps[1:]):
        (refresh if any(a.start < s < b.start for s in svd)
         else plain).append((a, b))
    return refresh, plain


def idle_between(run, lo: float, hi: float) -> float:
    return sum(max(min(e, hi) - max(s, lo), 0.0)
               for s, e in run.trace.gap_list)


def span(run, path: str) -> Tuple[int, float]:
    c, s = run.spans.get(path, [0, 0.0])
    return int(c), float(s)


def decode_rounds(run) -> List[List[int]]:
    """Per decode round of the stretch: the cached length of each lane."""
    return [dec for dec, _ in run.info.get("lanes", []) if dec]


def mean_or_none(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def median_or_none(xs):
    xs = list(xs)
    return median(xs) if xs else None
