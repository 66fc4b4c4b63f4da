"""``chip_smoke.py`` at CPU scale: the same phase functions the chip runs,
on reduced configs, so an API drift breaks here and not on the chip."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "platform cpu" in proc.stdout


def test_training_phase_healthy_then_degraded():
    clock = chip_smoke.CompileClock()
    _, hist = chip_smoke.run_training(
        chip_smoke.train_cfg(False), ShapeConfig("smoke", 32, 16, "train"),
        n_healthy=2, n_failed=2, clock=clock,
    )
    assert [r["failed"] > 0 for r in hist] == [False, False, True, True]


@pytest.mark.parametrize("paged", [False, True])
def test_serving_phase_records_and_replays(paged, capsys, tmp_path):
    chip_smoke.serve_phase(False, paged, jax.devices()[0],
                           chip_smoke.CompileClock(), out_dir=tmp_path)
    out = capsys.readouterr().out
    assert "replay bit-exact" in out
