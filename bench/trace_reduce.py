"""From a profiler trace (``.xplane.pb``) to device busy time, device time
by program and by operation, and idle gaps attributed to the host.

The arithmetic works on plain interval lists, so it is tested on hand-made
intervals and on a trace recorded on the CPU; :func:`device_events` picks
the chip's events out of a trace taken on the TPU.

* busy time is the union of the intervals in which an operation ran on a
  device, averaged over the devices used;
* a gap is an interval between two consecutive programs on one device; it
  is attributed to the innermost ``bench.*`` host annotation open at its
  midpoint (``host`` when none is).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANNOTATION_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float  # seconds
    dur: float    # seconds
    module: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str) -> List[Event]:
    """Every event of every line of every plane, times in seconds."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                out.append(Event(
                    plane.name, line.name, short_name(ev.name),
                    ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                    str(stats.get("hlo_module", "")),
                ))
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the TPU
    trace names an operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def device_events(events: Sequence[Event], line: str) -> Dict[str, List[Event]]:
    """Events of one line (``"XLA Ops"`` or ``"XLA Modules"``) on each TPU
    device plane, keyed by plane."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for ev in events:
        if ev.plane.startswith("/device:TPU:") and ev.line == line:
            out[ev.plane].append(ev)
    return dict(out)


def host_annotations(events: Sequence[Event]) -> List[Event]:
    return [ev for ev in events
            if not ev.plane.startswith("/device:")
            and ev.name.startswith(ANNOTATION_PREFIX)]


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the window ``[lo, hi]``; those outside it dropped."""
    out = []
    for ev in events:
        s, e = max(ev.start, lo), min(ev.end, hi)
        if e > s:
            out.append(Event(ev.plane, ev.line, ev.name, s, e - s, ev.module))
    return out


def by_name(events: Sequence[Event]) -> Dict[str, List[float]]:
    """``{name: [count, seconds]}``."""
    out: Dict[str, List[float]] = {}
    for ev in events:
        agg = out.setdefault(ev.name, [0, 0.0])
        agg[0] += 1
        agg[1] += ev.dur
    return out


def gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Intervals between consecutive (unioned) events on one device."""
    out = []
    end = None
    for ev in sorted(events, key=lambda e: e.start):
        if end is not None and ev.start > end:
            out.append((end, ev.start))
        end = ev.end if end is None else max(end, ev.end)
    return out


def attribute(gap_list: Sequence[Tuple[float, float]],
              annotations: Sequence[Event]) -> Dict[str, float]:
    """Seconds of gaps by the innermost annotation open at each midpoint."""
    out: Dict[str, float] = defaultdict(float)
    ann = sorted(annotations, key=lambda a: a.start)
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        best: Optional[Event] = None
        for a in ann:
            if a.start > mid:
                break
            if a.end >= mid and (best is None or a.start >= best.start):
                best = a
        out[best.name if best is not None else "host"] += e - s
    return dict(out)


@dataclass
class Reduction:
    window_s: float
    busy_s: float                 # averaged over devices
    programs: Dict[str, List[float]]  # XLA module -> [count, seconds]
    ops: Dict[str, List[float]]       # XLA op -> [count, seconds]
    module_events: List[Event]    # the first device's programs, in order
    gap_by_host: Dict[str, float]
    gap_list: List[Tuple[float, float]]

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        idle = sorted(self.gap_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v[1]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce_events(events: Sequence[Event], lo: float, hi: float,
                  ops_by_device: Dict[str, List[Event]],
                  modules_by_device: Dict[str, List[Event]]) -> Reduction:
    """Reduce the device events inside ``[lo, hi]`` (host clock, seconds)."""
    if not ops_by_device:
        raise ValueError("the trace holds no device operation")
    planes = sorted(ops_by_device)
    busy = [union_seconds((e.start, e.end)
                          for e in clip(ops_by_device[p], lo, hi))
            for p in planes]
    all_ops = [e for p in planes for e in clip(ops_by_device[p], lo, hi)]
    all_mods = [e for p in planes
                for e in clip(modules_by_device.get(p, []), lo, hi)]
    first = clip(modules_by_device.get(planes[0], []), lo, hi)
    gl = gaps(first)
    return Reduction(
        window_s=hi - lo, busy_s=sum(busy) / len(busy),
        programs=by_name(_strip_ids(all_mods)), ops=by_name(all_ops),
        module_events=sorted(first, key=lambda e: e.start),
        gap_by_host=attribute(gl, host_annotations(events)), gap_list=gl,
    )


def _strip_ids(events: Sequence[Event]) -> List[Event]:
    """``jit_step(123)`` -> ``jit_step``: a program's runs share one name."""
    return [Event(e.plane, e.line, e.name.split("(")[0], e.start, e.dur,
                  e.module) for e in events]


def reduce_trace(trace_dir: str, lo: float, hi: float) -> Reduction:
    events = load_events(find_xplane(trace_dir))
    return reduce_events(events, lo, hi, device_events(events, "XLA Ops"),
                         device_events(events, "XLA Modules"))
