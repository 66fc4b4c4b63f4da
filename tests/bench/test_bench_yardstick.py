"""The benchmark's yardstick: trace reduction, FLOP and byte counters, the
peaks table, the traffic generators, and the data-driven loading of cells
and metrics."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtiny as bt  # noqa: F401  (puts the repo root on sys.path)
from bench import cell as cells
from bench import flops, peaks, trace_reduce as tr, traffic
from bench.trace_reduce import Event

ROOT = bt.ROOT


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _ev(name, start, dur, plane="/device:TPU:0", line="XLA Ops"):
    return Event(plane, line, name, start, dur)


def test_union_gaps_and_attribution_on_hand_made_intervals():
    assert tr.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert tr.union_seconds([]) == 0.0
    mods = [_ev("jit_a(1)", 0.0, 1.0, line="XLA Modules"),
            _ev("jit_b(2)", 1.5, 1.0, line="XLA Modules"),
            _ev("jit_a(1)", 3.0, 0.5, line="XLA Modules")]
    assert tr.gaps(mods) == [(1.0, 1.5), (2.5, 3.0)]
    ann = [Event("/host:CPU", "python", "bench.window", 0.0, 4.0),
           Event("/host:CPU", "python", "bench.feed", 2.4, 0.4)]
    by = tr.attribute(tr.gaps(mods), ann)
    assert by == {"bench.window": pytest.approx(0.5),
                  "bench.feed": pytest.approx(0.5)}
    ops = [_ev("fusion.1", 0.0, 1.0), _ev("fusion.1", 1.5, 0.5),
           _ev("custom-call.2", 1.8, 0.4), _ev("fusion.1", 3.0, 0.5)]
    red = tr.reduce_events(ann, 0.0, 4.0, {"/device:TPU:0": ops},
                           {"/device:TPU:0": mods})
    assert red.window_s == 4.0
    assert red.busy_s == pytest.approx(1.0 + 0.7 + 0.5)
    assert red.programs == {"jit_a": [2, 1.5], "jit_b": [1, 1.0]}
    assert red.ops["fusion.1"] == [3, pytest.approx(2.0)]
    b = red.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert {k for k, _ in b["idle_gaps"]} == {"bench.window", "bench.feed"}


def test_clip_cuts_events_to_the_window():
    out = tr.clip([_ev("a", 0.0, 2.0), _ev("b", 3.0, 1.0)], 1.0, 3.5)
    assert [(e.name, e.start, e.dur) for e in out] == [
        ("a", 1.0, 1.0), ("b", 3.0, 0.5)]


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    events = tr.load_events(tr.find_xplane(str(tmp_path)))
    win = [e for e in tr.host_annotations(events) if e.name == "bench.window"]
    steps = [e for e in tr.host_annotations(events) if e.name == "bench.step"]
    assert len(win) == 1 and len(steps) == 3
    # on the CPU the XLA ops run on host threads: take them as the device's
    ops = [Event("/device:TPU:0", "XLA Ops", e.name, e.start, e.dur)
           for e in events if e.module and e.dur > 0]
    assert ops, "the CPU trace holds no XLA op"
    lo, hi = win[0].start, win[0].end
    red = tr.reduce_events(events, lo, hi, {"/device:TPU:0": ops},
                           {"/device:TPU:0": ops})
    assert 0 < red.busy_s <= red.window_s
    assert sum(v[1] for v in red.ops.values()) >= red.busy_s - 1e-9
    # the sleeps between steps are idle and fall under the window only
    assert red.gap_by_host.get("bench.window", 0.0) >= 0.02
    assert all(s >= lo - 1e-9 and e <= hi + 1e-9 for s, e in red.gap_list)


# ---------------------------------------------------------------------------
# FLOP and byte counters, against hand counts at reduced() size
# ---------------------------------------------------------------------------


def reduced_sizes():
    from repro.configs.base import get_config, reduced

    c = reduced(get_config("qwen3-0.6b"))
    return {"n_layers": c.n_layers, "d_model": c.d_model,
            "n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads,
            "head_dim": c.resolved_head_dim, "d_ff": c.d_ff,
            "vocab_size": c.vocab_size, "qk_norm": c.qk_norm,
            "dtype": "bfloat16"}


def test_flop_and_byte_counts_match_hand_counts():
    m = reduced_sizes()
    # L 2, d 128, H 4, KV 4, hd 32, F 256, V 512
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["head_dim"], m["d_ff"], m["vocab_size"]) == (
        2, 128, 4, 4, 32, 256, 512)
    layer = 128 * 128 + 2 * 128 * 128 + 128 * 128 + 3 * 128 * 256
    assert flops.layer_matmul_params(m) == layer == 163_840
    assert flops.matmul_params(m) == 2 * 163_840 + 128 * 512 == 393_216
    assert flops.train_flops_per_token(m, 64) == (
        6 * 393_216 + 3 * 4 * 2 * 4 * 32 * 64 * 0.5)
    assert flops.kv_bytes_per_token(m) == 2 * 2 * 4 * 32 * 2 == 1024
    ctx = [0, 63, 64]  # 1 + 1 + 2 pages of 64 tokens
    assert flops.paged_kernel_bytes(m, ctx, 64) == 4 * 64 * 1024 + 3 * 1024
    norms = 2 * 2 * 128 + 128 + 2 * 2 * 32
    assert flops.weight_bytes(m) == (393_216 + norms) * 2
    assert flops.decode_round_bytes(m, ctx, 64) == (
        (393_216 + norms) * 2 + 4 * 64 * 1024)
    assert flops.decode_round_flops(m, [5]) == (
        2 * 393_216 + 4 * 2 * 4 * 32 * 6)


def test_the_dense_cell_counts_what_it_counted_before():
    """The qwen3-0.6b file's figures, as the parent of the per-kind count
    gave them."""
    m = cells.model_sizes(cells.load_json(ROOT / "bench/configs/qwen3-0.6b.json"))
    assert flops.train_flops_per_token(m, 1024) == 3_928_227_840
    assert flops.matmul_params(m) == 595_984_384
    assert flops.weight_bytes(m) == 1_192_099_840
    assert flops.kv_bytes_per_token(m) == 114_688
    assert flops.decode_round_flops(m, [5, 100]) == 2_408_480_768
    assert flops.decode_round_bytes(m, [5, 100], 64) == 1_214_119_936
    assert flops.paged_kernel_bytes(m, [5, 100], 64) == 22_478_848


# hand-made maps of d 64, H 2, KV 1, hd 32 (attention 4,096 x 3 = 12,288
# weights), V 100, and where they appear: dense FFN F 96; 8 experts of 48,
# top 2; SSD expand 2 (inner 128), head 16 (8 heads), state 8, chunk 4
ATTN = {"d_model": 64, "n_heads": 2, "n_kv_heads": 1, "head_dim": 32,
        "vocab_size": 100, "d_ff": 96, "qk_norm": False, "dtype": "bfloat16"}
EXPERTS = {"moe.n_experts": 8, "moe.top_k": 2, "moe.d_ff_expert": 48}
SSD = {"ssm.expand": 2, "ssm.head_dim": 16, "ssm.d_state": 8, "ssm.chunk": 4}


def test_an_expert_layer_counts_its_top_k_experts_and_its_router():
    m = dict(ATTN, n_layers=1, **EXPERTS)
    attn = 64 * 2 * 32 + 2 * 64 * 1 * 32 + 2 * 32 * 64
    experts = 2 * 3 * 64 * 48 + 64 * 8  # top 2 of three matrices, router
    assert attn == 12_288 and experts == 18_944
    assert flops.layer_matmul_params(m) == 12_288 + 18_944
    assert flops.matmul_params(m) == 31_232 + 64 * 100 == 37_632
    assert flops.train_flops_per_token(m, 16) == (
        6 * 37_632 + 3 * 4 * 1 * 2 * 32 * 16 * 0.5)
    for decode in (lambda: flops.weight_bytes(m),
                   lambda: flops.decode_round_flops(m, [5]),
                   lambda: flops.decode_round_bytes(m, [5], 4)):
        with pytest.raises(ValueError, match="dense attention layers only"):
            decode()


def test_an_ssd_layer_counts_its_projections_and_its_scan():
    m = dict(ATTN, n_layers=1, family="ssm", **SSD)
    in_proj = 64 * (2 * 128 + 2 * 8 + 8)  # z, x, B, C, dt
    out_proj = 128 * 64
    assert flops.ssd_params(m) == in_proj + out_proj == 17_920 + 8_192
    assert flops.layer_matmul_params(m) == 26_112 + 3 * 64 * 96 == 44_544
    # intra-chunk (half counted), chunk states and readout, per token
    scan = (2 * 4 * 8 + 2 * 4 * 8 * 16) * 0.5 + 4 * 8 * 128
    assert flops.ssd_scan_flops_per_token(m) == scan == 4_640
    assert flops.train_flops_per_token(m, 16) == (
        6 * (44_544 + 6_400) + 3 * 4_640)  # no attention pair term
    assert flops.kv_bytes_per_token(m) == 0


def test_a_gelu_ffn_counts_two_matrices():
    m = dict(ATTN, n_layers=2, ffn_act="gelu")
    assert flops.layer_matmul_params(m) == 12_288 + 2 * 64 * 96 == 24_576
    assert flops.matmul_params(m) == 2 * 24_576 + 6_400 == 55_552
    norms = 2 * 2 * 64 + 64
    assert flops.weight_bytes(m) == (55_552 + norms) * 2
    assert flops.decode_round_flops(m, [5]) == (
        2 * 55_552 + 4 * 2 * 2 * 32 * 6)


def test_a_hybrid_pattern_counts_each_layer_by_its_kind():
    m = dict(ATTN, n_layers=8, family="hybrid", attn_every=4, attn_offset=1,
             **EXPERTS, **SSD, **{"moe.every": 2, "moe.offset": 0})
    assert flops.layer_pattern(m) == [
        ("ssm", True), ("attn", False), ("ssm", True), ("ssm", False),
        ("ssm", True), ("attn", False), ("ssm", True), ("ssm", False)]
    ssd_experts = 26_112 + 18_944
    attn_dense = 12_288 + 18_432
    ssd_dense = 26_112 + 18_432
    assert flops.matmul_params(m) == (
        4 * ssd_experts + 2 * attn_dense + 2 * ssd_dense + 6_400) == 337_152
    assert flops.train_flops_per_token(m, 16) == (
        6 * 337_152 + 3 * 4 * 2 * 2 * 32 * 16 * 0.5 + 3 * 6 * 4_640)
    assert flops.kv_bytes_per_token(m) == 2 * 2 * 1 * 32 * 2 == 256
    # one page of 4 tokens, q and the output of the two attention layers
    assert flops.paged_kernel_bytes(m, [0], 4) == 256 * 4 + 2 * 2 * 2 * 32 * 2


@pytest.mark.parametrize("arch", ["dense", "moe", "hybrid"])
def test_copied_flop_count_equals_the_programs(arch):
    """The program's own count (``launch/roofline.py``) charges every
    parameter that is no embedding row as a matmul weight; less the norms,
    the SSD convolution and its per-head scalars it is the benchmark's."""
    from functools import partial

    from bench import program
    from repro.configs.base import ShapeConfig
    from repro.launch.roofline import model_flops
    from repro.models.params import init_params

    config = bt.tiny_config() if arch == "dense" else bt.arch_config(arch)
    m = cells.model_sizes(config)
    cfg = program.model_config(config, m)
    shapes = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    other = sum(int(np.prod(v.shape)) for k, v in program.named_leaves(
        shapes).items() if k.split("/")[-1] in (
            "ln", "final_norm", "q_norm", "k_norm", "gate_norm", "conv_w",
            "conv_b", "A_log", "D", "dt_bias"))
    B, S = 4, 64
    theirs = model_flops(cfg, ShapeConfig("t", S, B, "train")) / (B * S)
    assert flops.train_flops_per_token(m, S) == pytest.approx(
        theirs - 6 * other, rel=1e-12)


# ---------------------------------------------------------------------------
# peaks and the refusal to run off the chip
# ---------------------------------------------------------------------------


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_raises():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_the_harness_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "qwen3-0.6b.train.failover", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"correct"' not in out.stdout


# ---------------------------------------------------------------------------
# traffic generators
# ---------------------------------------------------------------------------


MIX = {"rate_per_s": 5.0, "prompt_len": [64, 2048], "output_len": [32, 512],
       "length_dist": "longtail", "base_seed": 7}


def test_the_same_seed_gives_the_same_schedule():
    a = traffic.schedule(MIX, 2**31 + 17, 1000, 20.0, 10.0)
    b = traffic.schedule(MIX, 2**31 + 17, 1000, 20.0, 10.0)
    assert a == b and len(a) > 50
    assert all(x.at < y.at for x, y in zip(a, a[1:]))


def test_every_seed_offers_the_window_the_same_work():
    def window_work(seed):
        s = [d for d in traffic.schedule(MIX, seed, 1000, 20.0, 10.0)
             if d.at < 20.0]
        return sorted((len(d.prompt), d.max_new_tokens) for d in s)

    a, b = window_work(1), window_work(2)
    assert a == b
    sa = traffic.schedule(MIX, 1, 1000, 20.0, 10.0)
    sb = traffic.schedule(MIX, 2, 1000, 20.0, 10.0)
    assert [d.prompt for d in sa] != [d.prompt for d in sb]


def test_copied_generators_equal_the_programs():
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.serve.request import _draw_len

    seed = 2**31 + 5
    ours = traffic.BigramSource(512, seed).batch(3, 4, 16)
    theirs = SyntheticLM(512, DataConfig(seed=seed)).batch(3, 4, 16)
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(ours[k], theirs[k])
    r1, r2 = np.random.default_rng(9), np.random.default_rng(9)
    for _ in range(50):
        assert traffic.draw_len(r1, 64, 2048, "longtail") == _draw_len(
            r2, 64, 2048, "longtail")


def test_lateness_reports_how_late_requests_were_handed_over():
    late = traffic.lateness([0.0, 1.0, 2.0], [0.001, 1.0, 2.004])
    assert late["n"] == 3
    assert late["max_ms"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# data-driven: a cell, a traffic mix and a metric added by files alone
# ---------------------------------------------------------------------------


def test_a_cell_and_a_metric_load_from_files_alone(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs/tiny.json").write_text(json.dumps(bt.tiny_config()))
    (bench / "traffic/train.quick.json").write_text(
        json.dumps(bt.train_traffic(why="added by a file")))
    (bench / "metrics/quick.steps.py").write_text(
        "def read(run):\n    return float(run.info['steps'])\n")
    spec = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.train.quick", "config": "tiny",
                       "traffic": "train.quick", "chips": 1}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "quick.steps", "unit": "1",
                       "workloads": ["tiny.train.quick"]},
                      {"name": "other", "unit": "1",
                       "workloads": ["elsewhere"]}],
        "run_seconds": 10,
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.load_cell("tiny.train.quick", root=tmp_path, bench=bench)
    assert cell.traffic["why"] == "added by a file"
    assert cells.model_sizes(cell.config)["d_model"] == 64
    assert [e["name"] for e in cell.per_layer] == ["quick.steps"]
    assert cells.driver_for(cell).__name__.endswith("train")

    class Run:
        info = {"steps": 12}

    reader = cells.metric_reader("quick.steps", bench=bench)
    assert reader.read(Run()) == 12.0


def test_every_metric_and_cell_of_the_benchmark_has_its_files():
    spec = cells.load_json(ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.traffic["kind"] in ("train", "serve")
        assert cell.end_to_end and cell.per_layer
        cells.driver_for(cell)
        for e in cell.per_layer:
            assert callable(cells.metric_reader(e["name"]).read)


# ---------------------------------------------------------------------------
# per-layer readers on a hand-made training stretch
# ---------------------------------------------------------------------------


def test_training_readers_on_a_hand_made_stretch():
    """Five steps of 0.3 s (0.25 s on the device), an SVD refresh of 1 s
    between the third and the fourth."""
    mods, t = [], 0.0
    for i in range(5):
        mods.append(_ev("jit_step_fn", t, 0.25, line="XLA Modules"))
        t += 0.3
        if i == 2:
            mods.append(_ev("jit_svd", t, 0.9, line="XLA Modules"))
            t += 1.0
    ann = [Event("/host:CPU", "python", "bench.window", 0.0, t)]
    red = tr.reduce_events(ann, 0.0, t, {"/device:TPU:0": mods},
                           {"/device:TPU:0": mods})

    class Run:
        trace = red
        model = reduced_sizes()
        peaks = {"bf16_flops": 197e12}
        info = {"traced_steps": 5, "seq_len": 64, "tokens_per_step": 256}

    def read(name):
        return cells.metric_reader(name).read(Run())

    assert read("train_step.device_ms") == pytest.approx(250.0)
    assert read("train_loop.host_gap_ms") == pytest.approx(50.0)
    assert read("svd_refresh.ms") == pytest.approx(1000.0)
    per_step = flops.train_flops_per_token(Run.model, 64) * 256
    assert read("train_step.mfu") == pytest.approx(
        100 * per_step / 0.3 / 197e12)
    assert read("device.idle_share.train") == pytest.approx(
        100 * (1 - (5 * 0.25 + 0.9) / t))
