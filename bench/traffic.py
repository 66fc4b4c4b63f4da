"""Traffic generators: the yardstick's own copies, driven by a traffic file.

* :class:`BigramSource` is ``repro.data.pipeline.SyntheticLM`` copied: the
  training feed, one batch per step as a pure function of (seed, step).
* :func:`draw_len` is ``repro.serve.request._draw_len`` copied: log-normal
  long-tail lengths ("longtail") or uniform ones.
* :func:`schedule` is an open-loop request schedule on the wall clock:
  Poisson arrivals at a fixed rate with long-tail prompt and output
  lengths.  The same seed gives the same schedule, and every seed the same
  work in another order.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np


class BigramSource:
    """Sequences from a fixed random bigram chain with ``branching``
    successors per token (copied from the program's synthetic LM)."""

    def __init__(self, vocab_size: int, seed: int, branching: int = 4):
        self.vocab = vocab_size
        self.seed = seed
        self.branching = branching
        rng = np.random.default_rng(seed + 0xC0FFEE)
        self.successors = rng.integers(
            0, vocab_size, size=(vocab_size, branching), dtype=np.int64)

    def batch(self, step: int, batch_size: int, seq_len: int
              ) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed << 20) ^ step)
        toks = np.empty((batch_size, seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, size=batch_size)
        choices = rng.integers(0, self.branching, size=(batch_size, seq_len))
        for t in range(seq_len):
            toks[:, t + 1] = self.successors[toks[:, t], choices[:, t]]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def draw_len(rng: np.random.Generator, lo: int, hi: int, dist: str) -> int:
    if dist == "longtail" and hi > lo:
        x = lo + rng.lognormal(mean=0.0, sigma=1.0) * 0.15 * (hi - lo)
        return int(min(int(x), hi))
    return int(rng.integers(lo, hi + 1))


@dataclass(frozen=True)
class Due:
    """One request of a schedule: due ``at`` seconds after the window opens."""

    rid: int
    at: float
    prompt: Tuple[int, ...]
    max_new_tokens: int


def _segment(base: np.random.Generator, mix: dict, span_s: float):
    """Arrival gaps and (prompt, output) lengths of one stretch, from the
    mix's fixed base stream: every seed gets the same multiset."""
    dist = mix.get("length_dist", "longtail")
    gaps, lens = [], []
    t = 0.0
    while True:
        g = base.exponential(1.0 / mix["rate_per_s"])
        if t + g >= span_s:
            return gaps, lens
        t += g
        gaps.append(g)
        lens.append((draw_len(base, *mix["prompt_len"], dist),
                     draw_len(base, *mix["output_len"], dist)))


def schedule(mix: dict, seed: int, vocab_size: int, window_s: float,
             tail_s: float) -> List[Due]:
    """Requests due in ``[0, window_s + tail_s)``.

    ``mix`` keys: ``rate_per_s`` (Poisson arrivals), ``prompt_len`` and
    ``output_len`` (inclusive ``[lo, hi]``), ``length_dist`` and
    ``base_seed``.  The arrival gaps and lengths of the window, and of the
    tail after it, are drawn once from ``base_seed``; ``seed`` shuffles
    their order within each stretch and draws the prompt tokens.  So every
    seed offers the window the same work, in another order.
    """
    base = np.random.default_rng(mix["base_seed"])
    rng = np.random.default_rng(seed)
    out: List[Due] = []
    t0 = 0.0
    for span in (window_s, tail_s):
        gaps, lens = _segment(base, mix, span)
        order_g = rng.permutation(len(gaps))
        order_l = rng.permutation(len(lens))
        t = t0
        for i in range(len(gaps)):
            t += gaps[order_g[i]]
            plen, gen = lens[order_l[i]]
            prompt = tuple(int(x) for x in rng.integers(0, vocab_size,
                                                        size=plen))
            out.append(Due(len(out), t, prompt, gen))
        t0 += span
    return out


def lateness(due_at: List[float], handed_at: List[float]) -> Dict[str, float]:
    """How late the generator handed requests over, in milliseconds."""
    if not due_at:
        return {"n": 0, "p50_ms": 0.0, "max_ms": 0.0}
    late = np.maximum(np.asarray(handed_at) - np.asarray(due_at), 0.0) * 1e3
    return {"n": len(late), "p50_ms": float(np.median(late)),
            "max_ms": float(late.max())}
