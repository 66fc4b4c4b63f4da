"""A tiny cell on the CPU: the benchmark's drivers without the chip.

Used by the tests of the harness, its check and its faults.  The sizes are
far from any deployment; they only show the control flow and the
comparisons.
"""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell as cells  # noqa: E402

TINY_KEYS = {k: k for k in ("n_layers", "d_model", "n_heads", "n_kv_heads",
                            "head_dim", "d_ff", "vocab_size", "norm_eps",
                            "rope_theta", "qk_norm", "tie_embeddings",
                            "dtype")}


def tiny_config(**over) -> dict:
    c = {"name": "tiny", "repro_config": "qwen3-0.6b", "n_layers": 4,
         "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
         "d_ff": 128, "vocab_size": 256, "norm_eps": 1e-6,
         "rope_theta": 10000.0, "qk_norm": True, "tie_embeddings": True,
         "dtype": "bfloat16", "harness_keys": TINY_KEYS}
    real = cells.load_json(ROOT / "bench/configs/qwen3-0.6b.json")
    c["train_job"] = dict(real["train_job"], global_batch=4, seq_len=32,
                          n_stages=4, rank=8, svd_period=2)
    c.update(over)
    return c


# Sizes maps of the program's non-dense registry entries at their
# ``reduced()`` sizes, every size under its program field name: experts on
# every layer, and Jamba's hybrid cut to one period of 8 layers (one
# attention layer, seven SSD layers, experts on every second).
TINY_ARCHS = {
    "moe": ("qwen3-moe-30b-a3b", {
        "family": "moe", "n_layers": 2, "d_model": 128, "n_heads": 4,
        "n_kv_heads": 4, "head_dim": 32, "d_ff": 256, "vocab_size": 512,
        "norm_eps": 1e-5, "rope_theta": 1e6, "qk_norm": True,
        "tie_embeddings": False, "dtype": "bfloat16", "moe.n_experts": 8,
        "moe.top_k": 2, "moe.d_ff_expert": 64}),
    "hybrid": ("jamba-1.5-large-398b", {
        "family": "hybrid", "attn_every": 8, "attn_offset": 0,
        "n_layers": 8, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
        "head_dim": 32, "d_ff": 256, "vocab_size": 512, "norm_eps": 1e-5,
        "rope_theta": 10000.0, "qk_norm": False, "tie_embeddings": False,
        "dtype": "bfloat16", "moe.n_experts": 8, "moe.top_k": 2,
        "moe.d_ff_expert": 64, "moe.every": 2, "moe.offset": 1,
        "ssm.d_state": 16, "ssm.expand": 2, "ssm.head_dim": 16,
        "ssm.chunk": 32}),
}

# what tests/bench/recording_reference.py was called with, and the leaf
# names it answers for
REFERENCE_CALLS: list = []
REFERENCE_LEAVES: list = []


def arch_config(arch: str, **over) -> dict:
    """A configuration file for ``TINY_ARCHS[arch]`` and the tiny job."""
    repro, sizes = TINY_ARCHS[arch]
    c = tiny_config(name=f"tiny-{arch}", repro_config=repro,
                    harness_keys={k: k for k in sizes})
    c.update(sizes)
    c.update(over)
    return c


def train_traffic(name="train.failover", **over) -> dict:
    t = cells.load_json(ROOT / f"bench/traffic/{name}.json")
    if t["failures"]:
        t["failures"] = [[0, 0], [1, 1], [2, 2], [3, 3]]
    t.update(over)
    return t


def serve_traffic(**over) -> dict:
    t = cells.load_json(ROOT / "bench/traffic/serve.chat.json")
    t.update(rate_per_s=20.0, prompt_len=[4, 40], output_len=[2, 12],
             tail_s=20, engine=dict(t["engine"], max_slots=4, page_size=8,
                                    pages_per_slot=8),
             check_tokens=40, trace_stretch_s=0.2)
    t.update(over)
    return t


class Ctx:
    """Stands in for ``bench/run.py``'s context: no device check."""

    def __init__(self, config, traffic, seed=3, trace=False):
        self.cell = cells.Cell(name="tiny." + traffic["kind"], chips=1,
                               config=copy.deepcopy(config),
                               traffic=copy.deepcopy(traffic),
                               end_to_end=[], per_layer=[])
        self.seed, self.trace = seed, trace
        self.model = cells.model_sizes(config)
        self.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        self.timers = {}
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)

    def start_trace(self):
        pass

    def stop_trace(self):
        pass


def run_cell(ctx, seconds=1.0):
    """set-up, window and check; returns (window result, correct, checks)."""
    driver = cells.load_module(ROOT / "bench/drivers"
                               / f"{ctx.cell.traffic['kind']}.py")
    st = driver.setup(ctx)
    res = driver.window(st, seconds, ctx.trace)
    correct, checks = driver.check(st)
    return res, correct, checks


def dumps(x) -> str:
    return json.dumps(x, default=str)
