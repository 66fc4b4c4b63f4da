"""Serving cells: open-loop traffic through ``ReplicaSet.step``.

Set-up makes the weights on the chip in one jitted call from the seed,
builds the replica set, and warms up every prefill bucket the mix's prompt
lengths reach and the decode round.  The window then hands the replica set,
step by step, every request whose due time on the wall clock has passed,
and keeps going after the window closes (arrivals included) until each
request due in the window has finished, at most ``tail_s`` seconds.

* ``ttft_p95_ms``: from each window request's due time to its first token
  back on the host; one with no first token by the end of the run counts
  with all the time it waited, longer than any request served;
* ``tpot_p95_ms``: (last-token time - first-token time) / (tokens - 1);
* ``serve_tokens_per_s``: tokens back on the host inside the window, over
  the window.

The check runs the plain reference over a sample of the finished requests
(the longest among them) and reads the widest gap by which a served token's
logit lies below the reference's best at its position.
"""
from __future__ import annotations

import math
import time
from functools import partial
from typing import Dict, List, Tuple

import numpy as np

from bench import cell as cells
from bench import program
from bench.traffic import Due, lateness, schedule

WARM_RID = 1 << 40


class State:
    pass


def p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(math.ceil(0.95 * len(v)) - 1, 0)]


def build(ctx, seed: int):
    """(replica set, params) for one seed."""
    import jax

    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_flags, build_rules
    from repro.models.params import init_params
    from repro.serve.engine import EngineConfig
    from repro.serve.replicas import ReplicaSet

    cfg = program.model_config(ctx.cell.config, ctx.model)
    mesh = make_host_mesh()
    par = ParallelConfig(fsdp=False)
    rules = build_rules(cfg, mesh, par)
    flags = build_flags(cfg, par, mesh)
    params = jax.jit(partial(init_params, cfg))(jax.random.PRNGKey(seed))
    ecfg = EngineConfig(**ctx.cell.traffic["engine"])
    rset = ReplicaSet(cfg, params, rules, flags, ecfg,
                      n_replicas=ctx.cell.traffic.get("replicas", 1),
                      chaos_seed=seed)
    jax.block_until_ready(params)
    return rset, params


def warm_up(rset, traffic: dict) -> None:
    """One request per prefill bucket of the mix, two tokens each: every
    prefill and scatter shape and the decode round compile here."""
    from repro.serve.request import Request

    ps = traffic["engine"]["page_size"]
    lo, hi = traffic["prompt_len"]
    reqs = [Request(WARM_RID + k, 0, tuple([1] * (k * ps)), 2)
            for k in range(-(-lo // ps), -(-hi // ps) + 1)]
    left = {r.rid for r in reqs}
    t = 0
    evs = rset.step(t, reqs)
    while True:
        left -= {e.req for e in evs if e.kind == "complete"}
        if not left:
            return
        t += 1
        evs = rset.step(t, [])


def setup(ctx) -> State:
    st = State()
    st.ctx = ctx
    # a reference that does not compute the file's sizes stops the run here
    cells.reference_for(ctx.cell.config)
    t0 = time.perf_counter()
    st.rset, st.params = build(ctx, ctx.seed)
    ctx.timers["state_init_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm_up(st.rset, ctx.cell.traffic)
    ctx.timers["warmup_s"] = time.perf_counter() - t0
    st.t = 1 << 20  # engine steps of the window count from here
    return st


def idle(rset) -> bool:
    return not rset.queue and all(
        e is None or e.n_active == 0 for e in rset.engines.values())


def run_window(st: State, seconds: float, trace: bool, sched: List[Due]):
    """Drive the open loop; returns per-request token times and the lanes
    of every engine step inside the traced stretch."""
    import jax

    from repro import obs
    from repro.serve.request import Request

    traffic = st.ctx.cell.traffic
    tail = traffic["tail_s"]
    rset = st.rset
    win = {d.rid for d in sched if d.at < seconds}
    times: Dict[int, List[float]] = {d.rid: [] for d in sched}
    st.tokens = {d.rid: [] for d in sched}
    plen = {d.rid: len(d.prompt) for d in sched}
    handed: List[float] = []
    done = set()
    stretch = traffic.get("trace_stretch_s", 3.0)
    t_lo = max(seconds / 2 - stretch / 2, 0.0)
    tracing = None
    lanes = []  # per traced step: (decode contexts, prefilled prompt lengths)
    st.queue = []  # (seconds, requests waiting) after every step
    spans_before = None
    i = 0
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter() - t_open
        if trace and tracing is None and now >= t_lo:
            spans_before = program.span_totals()
            st.ctx.start_trace()
            tracing = jax.profiler.TraceAnnotation("bench.window")
            tracing.__enter__()
            t_trace = now
        arrivals = []
        while i < len(sched) and sched[i].at <= now:
            d = sched[i]
            arrivals.append(Request(d.rid, st.t, d.prompt, d.max_new_tokens))
            handed.append(now)
            i += 1
        with jax.profiler.TraceAnnotation("bench.replicas.step"):
            with obs.span("router.step"):
                evs = rset.step(st.t, arrivals)
        back = time.perf_counter() - t_open
        st.t += 1
        st.queue.append((back, len(rset.queue)))
        dec, pre = [], []
        for e in evs:
            if e.kind == "token":
                n = len(times[e.req])
                if n:
                    dec.append(plen[e.req] + n - 1)
                else:
                    pre.append(plen[e.req])
                times[e.req].append(back)
                st.tokens[e.req].append(int(e.token))
            elif e.kind == "complete":
                done.add(e.req)
        if tracing:
            lanes.append((dec, pre))
            if back - t_trace >= stretch:
                tracing.__exit__(None, None, None)
                st.ctx.stop_trace()
                st.spans = program.span_delta(program.span_totals(),
                                              spans_before)
                tracing = False
        if back >= seconds and (win <= done or back >= seconds + tail):
            break
        if idle(rset):
            if i >= len(sched):
                break
            time.sleep(max(sched[i].at - (time.perf_counter() - t_open), 0))
    if tracing:
        tracing.__exit__(None, None, None)
        st.ctx.stop_trace()
        st.spans = program.span_delta(program.span_totals(), spans_before)
    st.t_end = time.perf_counter() - t_open
    due = [d.at for d in sched[:len(handed)]]
    return win, times, lanes, lateness(due, handed)


def window(st: State, seconds: float, trace: bool) -> dict:
    ctx = st.ctx
    traffic = ctx.cell.traffic
    sched = schedule(traffic, ctx.seed, ctx.model["vocab_size"], seconds,
                     traffic["tail_s"])
    st.spans = {}
    win, times, lanes, late = run_window(st, seconds, trace, sched)
    st.sched = {d.rid: d for d in sched}
    st.times = times
    # a request still without its first or last token when the loop stops
    # counts with the time it had waited by then, longer than any served
    ttft, tpot, failed = [], [], 0
    for rid in sorted(win):
        d, ts = st.sched[rid], times[rid]
        ttft.append(((ts[0] if ts else st.t_end) - d.at) * 1e3)
        if len(ts) < d.max_new_tokens:
            failed += 1
            tpot.append((st.t_end - (ts[0] if ts else d.at)) * 1e3)
        elif d.max_new_tokens > 1:
            tpot.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    n_tok = sum(1 for ts in times.values() for x in ts if x <= seconds)
    acct = st.rset.acct
    ctx.log(f"serve: {len(win)} requests due in the window, {failed} "
            f"unfinished; {n_tok} tokens in the window; generator late "
            f"p50 {late['p50_ms']:.3f} ms max {late['max_ms']:.3f} ms; "
            f"snapshots {acct['n_snapshots']} kills {acct['n_kills']}")
    info = {"lanes": lanes, "page_size": traffic["engine"]["page_size"]}
    return {"metrics": {"ttft_p95_ms": p95(ttft), "tpot_p95_ms": p95(tpot),
                        "serve_tokens_per_s": n_tok / seconds},
            "attempted": len(win), "failed": failed, "info": info,
            "spans": st.spans}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def sample(st: State) -> List[Tuple[Tuple[int, ...], List[int]]]:
    """(prompt, served tokens) of a seeded sample of finished requests: the
    one with the most served tokens, then others until ``check_tokens``."""
    traffic = st.ctx.cell.traffic
    fin = sorted(rid for rid, d in st.sched.items()
                 if len(st.times[rid]) >= d.max_new_tokens)
    if not fin:
        return []
    served = {rid: st.tokens[rid] for rid in fin}
    longest = max(fin, key=lambda r: (len(served[r]), -r))
    rng = np.random.default_rng(st.ctx.seed)
    order = [longest] + [r for r in rng.permutation(fin).tolist()
                         if r != longest]
    out, n = [], 0
    for rid in order[:traffic["check_max_requests"]]:
        out.append((st.sched[rid].prompt, list(served[rid])))
        n += len(served[rid])
        if n >= traffic["check_tokens"]:
            break
    return out


def reference_gaps(ctx, seed: int, pairs, quant=None) -> Dict[str, float]:
    """Widest gap of a served token below the reference's best; with
    ``quant`` also the widest gap of the control's own first choice."""
    import jax.numpy as jnp

    traffic = ctx.cell.traffic
    m = ctx.model
    T = traffic["prompt_len"][1] + traffic["output_len"][1]
    n = traffic["output_len"][1]
    reference = cells.reference_for(ctx.cell.config)
    w = reference.init_weights(m, seed)
    ref = reference.make_seq_logits(m)
    ctl = reference.make_seq_logits(m, quant) if quant else None
    gap = ctl_gap = 0.0
    agree = total = 0
    for prompt, served in pairs:
        toks = np.zeros((1, T), np.int32)
        seq = list(prompt) + served[:-1]
        toks[0, :len(seq)] = seq
        start = jnp.int32(len(prompt) - 1)
        lg = np.asarray(ref(w, jnp.asarray(toks), start, n))[:len(served)]
        best = lg.max(axis=1)
        got = lg[np.arange(len(served)), served]
        gap = max(gap, float((best - got).max()))
        agree += int((lg.argmax(axis=1) == np.asarray(served)).sum())
        total += len(served)
        if ctl is not None:
            lq = np.asarray(ctl(w, jnp.asarray(toks), start, n))[:len(served)]
            pick = lq.argmax(axis=1)
            ctl_gap = max(ctl_gap, float(
                (best - lg[np.arange(len(served)), pick]).max()))
    ctx.log(f"serve check: {len(pairs)} requests, {total} served tokens, "
            f"{agree} equal to the reference's first choice")
    out = {"token_gap": gap}
    if quant:
        out["control_gap"] = ctl_gap
    return out


def check(st: State) -> Tuple[bool, dict]:
    import gc

    pairs = sample(st)
    del st.rset, st.params
    gc.collect()
    limits = st.ctx.cell.traffic["limits"]
    if not pairs:
        return False, {"token_gap": {"value": None,
                                     "limit": limits["token_gap"]}}
    gaps = reference_gaps(st.ctx, st.ctx.seed, pairs)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}
    # a limit not yet set from the chip's readings judges nothing
    return all(limits[k] is not None and v <= limits[k]
               for k, v in gaps.items()), checks
