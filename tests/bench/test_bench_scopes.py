"""The train step's scopes and the program's spans on the profiler's clock:
the arithmetic of ``bench/scopes.py`` on a hand-made stretch, the scopes in
the lowered train step, and the spans in a profiler trace recorded on the
CPU."""
from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import pytest

import benchtiny as bt
from bench import cell as cells
from bench import scopes as sc
from bench import trace_reduce as tr
from bench.trace_reduce import Event

DEV = "/device:TPU:0"
STEP = "jit(step_fn)"
FWD = STEP + "/jvp()/while/body/closed_call"
BWD = STEP + "/transpose(jvp())/while/body/closed_call"


# ---------------------------------------------------------------------------
# scope paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("component,scope", [
    ("attn", "attn"),
    ("jvp(attn)", "attn"),
    ("transpose(jvp(head))", "head"),
    ("checkpoint(ffn)", "ffn"),
    ("remat(lowrank_wgrad)", "lowrank_wgrad"),
    ("jvp()", ""),
    ("bkgqs,bskh->bqkgh", "bkgqs,bskh->bqkgh"),
])
def test_a_scope_matches_in_every_transformed_form(component, scope):
    assert sc.component_scope(component) == scope


def test_the_outermost_step_scope_owns_an_op():
    path = BWD + "/ffn/ffn/checkpoint/lowrank_wgrad/dot_general"
    assert sc.step_scope(path) == "ffn"
    assert sc.has_scope(path, ["lowrank_wgrad"])
    assert not sc.has_scope(path, ["attn", "head"])
    assert sc.step_scope(STEP + "/transpose(jvp())/while") is None


def test_op_names_come_from_the_hlo_text():
    text = """
  %fusion.12 = bf16[4]{0} fusion(%p.1), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step_fn)/jvp(head)/mul" stack_frame_id=3}
  ROOT %tuple.4 = (f32[]) tuple(%x), metadata={op_name="jit(step_fn)/optimizer/add"}
  %p.1 = bf16[4]{0} parameter(0)
"""
    assert sc.op_names_from_hlo(text) == {
        "fusion.12": "jit(step_fn)/jvp(head)/mul",
        "tuple.4": "jit(step_fn)/optimizer/add"}
    ops = [sc.Op(DEV, "jit_step_fn", "fusion.12", 0.0, 1.0),
           sc.Op(DEV, "jit_other", "fusion.12", 1.0, 1.0),
           sc.Op(DEV, "jit_step_fn", "copy.1", 2.0, 1.0)]
    named = sc.with_op_names(ops, "jit_step_fn", sc.op_names_from_hlo(text))
    assert [o.op_name for o in named] == ["jit(step_fn)/jvp(head)/mul", "", ""]


def test_an_op_runs_in_the_program_around_it():
    """A TPU trace names an op by its HLO instruction alone: its module is
    the ``XLA Modules`` event that covers it on the same device."""
    ev = [Event(DEV, "XLA Modules", "jit_step_fn(77)", 0.0, 1.0),
          Event(DEV, "XLA Modules", "jit_svd_projection(78)", 1.5, 1.0),
          Event(DEV, "XLA Ops", "fusion.1", 0.2, 0.3),
          Event(DEV, "XLA Ops", "fusion.1", 1.6, 0.3),
          Event(DEV, "XLA Ops", "copy.2", 1.2, 0.1),
          Event("/device:TPU:1", "XLA Ops", "fusion.1", 0.2, 0.3),
          Event("/host:CPU", "python", "trainer.step", 0.0, 3.0)]
    ops = sc.device_ops(ev)
    assert [(o.name, o.module) for o in ops[DEV]] == [
        ("fusion.1", "jit_step_fn"), ("fusion.1", "jit_svd_projection"),
        ("copy.2", "")]
    assert [o.module for o in ops["/device:TPU:1"]] == [""]


def test_self_time_takes_nested_ops_out_of_their_containers():
    ops = [sc.Op(DEV, "m", "while.1", 0.0, 10.0),
           sc.Op(DEV, "m", "cond.1", 1.0, 4.0),
           sc.Op(DEV, "m", "fusion.1", 1.5, 2.0),
           sc.Op(DEV, "m", "fusion.2", 6.0, 3.0),
           sc.Op(DEV, "m", "fusion.3", 11.0, 1.0)]
    got = {o.name: s for o, s in sc.self_times(ops)}
    assert got == pytest.approx({"while.1": 10.0 - 4.0 - 3.0,
                                 "cond.1": 4.0 - 2.0, "fusion.1": 2.0,
                                 "fusion.2": 3.0, "fusion.3": 1.0})


def test_a_gap_is_split_by_the_innermost_annotation_over_time():
    anns = [Event("/host:CPU", "python", "trainer.step", 0.0, 10.0),
            Event("/host:CPU", "python", "trainer.feed", 2.0, 1.0),
            Event("/host:CPU", "python", "trainer.dispatch", 4.0, 2.0),
            Event("/host:CPU", "python", "trainer.record", 12.0, 1.0)]
    by = sc.attribute_innermost([(1.0, 5.0), (9.0, 12.5)], anns)
    assert by == pytest.approx({"trainer.step": 2.0 + 1.0,
                                "trainer.feed": 1.0,
                                "trainer.dispatch": 1.0, "host": 2.0,
                                "trainer.record": 0.5})


# ---------------------------------------------------------------------------
# the new metrics on a hand-made training stretch
# ---------------------------------------------------------------------------


# one train step's device work: (op, scope path, start, duration) in ms
# from the step's start, containers first; self times in the comments.
# The top-level ops tile the step's 250 ms.
STEP_OPS = [
    ("fusion.1", STEP + "/jvp(embed)/select_n", 0, 5),                 # 5
    ("while.1", STEP + "/jvp()/while", 5, 60),                          # 10
    ("fusion.2", FWD + "/attn/dot_general", 6, 20),                     # 20
    ("fusion.3", FWD + "/ffn/dot_general", 27, 30),                     # 30
    ("while.2", STEP + "/transpose(jvp())/while", 65, 122),             # 7
    ("fusion.4", BWD + "/attn/while/body/closed_call/checkpoint/"
     "flashsubst/bqkgh,bskh->bkgqs/dot_general", 66, 40),               # 40
    ("cond.1", BWD + "/ffn/ffn/checkpoint/cond", 108, 75),              # 5
    ("fusion.5", BWD + "/ffn/ffn/checkpoint/rematted_computation/"
     "dot_general", 109, 30),                                           # 30
    ("fusion.6", BWD + "/ffn/ffn/checkpoint/lowrank_wgrad/dot_general",
     140, 40),                                                          # 40
    ("fusion.7", STEP + "/transpose(jvp(head))/while/body/closed_call/"
     "checkpoint/dot_general", 187, 50),                                # 50
    ("scatter.1", STEP + "/transpose(jvp(embed))/scatter-add", 237, 8),  # 8
    ("fusion.8", STEP + "/optimizer/mul", 245, 4),                       # 4
    ("copy.1", STEP + "/add_any", 249, 1),                               # 1
]
STEP_MS = 250.0
GAP_MS = 50.0


def hand_made_stretch():
    """Five steps of 300 ms (250 on the device), an SVD refresh of 1 s
    between the third and the fourth (two ``svd_projection`` programs of
    0.4 s), each step's host phases as program spans."""
    mods, ops, host, t = [], [], [], 0.0
    for i in range(5):
        mods.append(Event(DEV, "XLA Modules", "jit_step_fn", t,
                          STEP_MS / 1e3, "jit_step_fn"))
        for name, path, s, d in STEP_OPS:
            ops.append(sc.Op(DEV, "jit_step_fn", name, t + s / 1e3, d / 1e3,
                             path))
        # the step's loss is read as the device finishes; then the record
        # and the next step's feed, masks and dispatch
        end = t + STEP_MS / 1e3
        host += [Event("/host:CPU", "python", "trainer.read", t, STEP_MS / 1e3),
                 Event("/host:CPU", "python", "trainer.record", end, 0.01),
                 Event("/host:CPU", "python", "trainer.feed", end + 0.015, 0.02),
                 Event("/host:CPU", "python", "trainer.dispatch",
                       end + 0.04, 0.01)]
        t += 0.3
        if i == 2:
            for j in range(2):
                mods.append(Event(DEV, "XLA Modules", "jit_svd_projection",
                                  t + 0.45 * j, 0.4, "jit_svd_projection"))
                ops.append(sc.Op(DEV, "jit_svd_projection", "custom-call.1",
                                 t + 0.45 * j, 0.4, "jit(svd_projection)/svd"))
            t += 1.0
    host.append(Event("/host:CPU", "python", "bench.window", 0.0, t))
    ann = [e for e in host if e.name.startswith("bench.")]
    dev_ops = [Event(DEV, "XLA Ops", o.name, o.start, o.dur, o.module)
               for o in ops]
    return mods, ops, dev_ops, host, ann, t


class Run:
    model = None
    peaks = {"bf16_flops": 197e12}
    info = {"traced_steps": 5, "seq_len": 64, "tokens_per_step": 256}
    # per step 0.3 s: 0.25 s waiting on the loss, 0.05 s of host phases
    spans = {"trainer.step": [5, 1.5],
             "trainer.step/trainer.read": [5, 1.25],
             "trainer.step/trainer.feed": [5, 0.1],
             "trainer.step/controller.apply_chaos": [5, 0.01]}


def read_all(names, red):
    from test_bench_yardstick import reduced_sizes

    Run.trace = red
    Run.model = reduced_sizes()
    return {n: cells.metric_reader(n).read(Run()) for n in names}


OLD = ("train_step.device_ms", "train_loop.host_gap_ms", "svd_refresh.ms",
       "train_step.mfu", "device.idle_share.train")


def test_new_metrics_on_a_hand_made_stretch():
    mods, ops, dev_ops, host, ann, t = hand_made_stretch()
    red = tr.reduce_events(host, 0.0, t, {DEV: dev_ops}, {DEV: mods})
    # the new readers that the harness runs
    got = read_all(("trainer.host_ms_per_step", "svd_refresh.device_ms"), red)
    assert got["trainer.host_ms_per_step"] == pytest.approx(50.0)
    assert got["svd_refresh.device_ms"] == pytest.approx(800.0)
    # the scope metrics: self time under each scope, per step
    selfs = sc.module_self_times({DEV: ops}, "jit_step_fn")
    assert sum(s for _, s in selfs) == pytest.approx(5 * STEP_MS / 1e3)
    assert sc.scope_metrics(selfs, 5) == pytest.approx({
        "train_step.attn_ms": 20 + 40,
        "train_step.ffn_ms": 30 + 5 + 30 + 40,
        "train_step.vocab_ms": 5 + 50 + 8,
        "train_step.optimizer_ms": 4,
        "mecefo.lowrank_wgrad_ms": 40,
    })
    split = {k: v * 1e3 / 5 for k, v in sc.split_by_step_scope(selfs).items()}
    assert split == pytest.approx({"embed": 13, "attn": 60, "ffn": 105,
                                   "head": 50, "optimizer": 4,
                                   "other": 10 + 7 + 1})
    # the gaps after steps 0-3 (50 ms each; the SVD programs' gaps of 50
    # and 150 ms hold no span), by the innermost annotation
    anns = sc.annotations(host, ["trainer.read", "trainer.record",
                                 "trainer.feed", "trainer.dispatch"])
    assert sc.attribute_innermost(red.gap_list, anns) == pytest.approx({
        "trainer.record": 4 * 0.01, "trainer.feed": 4 * 0.02,
        "trainer.dispatch": 4 * 0.01,
        "bench.window": 4 * 0.01 + 0.05 + 0.15})


def test_the_existing_readers_read_what_they_read_without_the_new_events():
    """The scoped ops nested in their containers and the program spans
    leave the five readers where the programs alone put them."""
    mods, _, dev_ops, host, ann, t = hand_made_stretch()
    with_new = read_all(OLD, tr.reduce_events(host, 0.0, t, {DEV: dev_ops},
                                              {DEV: mods}))
    without = read_all(OLD, tr.reduce_events(ann, 0.0, t, {DEV: mods},
                                             {DEV: mods}))
    assert with_new == pytest.approx(without)
    assert with_new["train_step.device_ms"] == pytest.approx(STEP_MS)
    assert with_new["train_loop.host_gap_ms"] == pytest.approx(GAP_MS)
    assert with_new["svd_refresh.ms"] == pytest.approx(1000.0)
    assert with_new["device.idle_share.train"] == pytest.approx(
        100 * (1 - (5 * 0.25 + 0.8) / t))


def test_the_new_readers_give_nothing_where_the_program_has_no_such_data():
    mods, _, dev_ops, host, ann, t = hand_made_stretch()
    old = [Event(DEV, "XLA Modules", "jit_svd", m.start, m.dur)
           if "svd" in m.name else m for m in mods]
    red = tr.reduce_events(ann, 0.0, t, {DEV: dev_ops}, {DEV: old})
    Run.spans, spans = {"trainer.step": [5, 1.5],
                        "trainer.step/controller.apply_chaos": [5, 0.01]}, \
        Run.spans
    try:
        got = read_all(("trainer.host_ms_per_step", "svd_refresh.device_ms"),
                       red)
    finally:
        Run.spans = spans
    assert got == {"trainer.host_ms_per_step": None,
                   "svd_refresh.device_ms": None}


# ---------------------------------------------------------------------------
# the scopes in the lowered train step
# ---------------------------------------------------------------------------


def test_the_lowered_train_step_names_its_scopes():
    """The tiny cell's train step (dynamic NDB, remat "ffn", a degraded
    rank): every scope is in the HLO ``op_name`` metadata, every matmul
    carries one, and the low-rank Wgrad is there."""
    from bench.drivers import train as drv
    from repro.core.ndb import plan_to_masks
    from repro.data.pipeline import make_batch

    ctx = bt.Ctx(bt.tiny_config(), bt.train_traffic())
    job = drv.job_of(ctx.cell.config, ctx.cell.traffic)
    assert job["remat"] == "ffn" and job["mecefo_mode"] == "dynamic"
    trainer = drv.build_trainer(ctx, job)
    trainer.controller.apply_chaos(trainer.process.step(0))
    key = trainer._step_key()
    assert key == ("dynamic",)
    batch = make_batch(trainer.cfg, trainer.shape, 0, source=trainer.source,
                       seed=trainer.seed)
    keep, w = plan_to_masks(trainer._mask_plan(), trainer.cfg,
                            trainer.shape.global_batch)
    assert 0 < keep.mean() < 1
    with trainer.mesh:
        text = trainer._get_step(key).lower(
            trainer.state, batch, {"keep": keep, "example_weight": w}
        ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    present = {s for n in names for s in sc.scopes_in(n)}
    assert set(sc.STEP_SCOPES) | {sc.LOWRANK} <= present
    dots = [n for n in names if n.endswith("/dot_general")]
    assert dots
    unscoped = [n for n in dots if not sc.has_scope(
        n, ("embed", "attn", "ffn", "head", sc.LOWRANK))]
    assert unscoped == []
    assert any(sc.has_scope(n, [sc.LOWRANK]) for n in dots)


def test_the_refresh_is_one_program_named_svd_projection():
    from repro.core.lowrank import svd_projection

    w = jnp.ones((2, 16, 8), jnp.bfloat16)
    text = svd_projection.lower(w, 4).as_text()
    assert re.search(r"module @jit_svd_projection", text)
    assert svd_projection(w, 4).shape == (2, 16, 4)


# ---------------------------------------------------------------------------
# the program's spans in a profiler trace
# ---------------------------------------------------------------------------


def test_a_span_is_a_host_event_the_attribution_picks_as_innermost(tmp_path):
    from repro.obs.spans import Tracer
    from repro.obs.catalog import SPANS

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    spans, off = Tracer(), Tracer()
    off.enabled = False
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("trainer.step"):
        with spans.span("trainer.dispatch"):
            r = f(x)
        with spans.span("trainer.read"):
            float(r)
        with spans.span("trainer.record"):
            time.sleep(0.02)
    with off.span("trainer.feed"):
        time.sleep(0.001)
    jax.profiler.stop_trace()
    events, _ = sc.load(tr.find_xplane(str(tmp_path)))
    anns = sc.annotations(events, SPANS)
    got = {a.name: a for a in anns}
    assert set(got) == {"trainer.step", "trainer.dispatch", "trainer.read",
                        "trainer.record"}
    rec = got["trainer.record"]
    assert rec.dur >= 0.02
    assert got["trainer.step"].start <= rec.start and (
        rec.end <= got["trainer.step"].end)
    by = sc.attribute_innermost([(rec.start, rec.end)], anns)
    assert by == pytest.approx({"trainer.record": rec.dur})
    assert spans.aggregates["trainer.step/trainer.record"][0] == 1
