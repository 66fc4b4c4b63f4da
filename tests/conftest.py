import os

# Smoke tests and benches must see the real (1-device) CPU platform —
# XLA_FLAGS device-count forcing belongs to the dry-run ONLY.
os.environ.pop("XLA_FLAGS", None)

import jax  # noqa: E402

# The persistent compile cache stays off in tests: entry points that the
# suite calls (``launch/train.py:main``) would otherwise write to it, and
# the described-TPU compiles in test_chip_compile.py must stay silent.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig  # noqa: E402
from repro.parallel.sharding import ShardingRules  # noqa: E402


def require_hypothesis():
    """Shared guard for the optional ``hypothesis`` dependency.

    Call at module top before ``from hypothesis import ...``: skips the whole
    module when the [test] extra isn't installed, and returns the module so
    callers can grab settings/strategies from the return value if preferred.
    """
    return pytest.importorskip(
        "hypothesis", reason="property tests need the [test] extra"
    )


@pytest.fixture(scope="session")
def local_rules():
    """No-mesh sharding rules (everything replicated) for 1-device tests."""
    return ShardingRules(
        batch=(), embed=None, heads=None, kv_heads=None, mlp=None,
        vocab=None, expert=None, ssm_inner=None,
    )


TINY_DENSE = ModelConfig(
    name="tiny-dense", n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=128, vocab_size=256, dtype="float32",
)
TINY_MOE = ModelConfig(
    name="tiny-moe", family="moe", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=0, vocab_size=256, dtype="float32",
    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, capacity_factor=4.0),
)
TINY_SSM = ModelConfig(
    name="tiny-ssm", family="ssm", n_layers=4, d_model=64, n_heads=1,
    n_kv_heads=1, d_ff=0, vocab_size=256, dtype="float32",
    ssm=SSMConfig(d_state=16, head_dim=16, chunk=8),
)
TINY_HYBRID = ModelConfig(
    name="tiny-hybrid", family="hybrid", n_layers=8, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=256, dtype="float32", attn_every=4,
    moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32, every=2, offset=1,
                  capacity_factor=4.0),
    ssm=SSMConfig(d_state=16, head_dim=16, chunk=8),
)


@pytest.fixture(params=[
    "dense", "moe", "ssm",
    # the hybrid interleave is the slowest tiny config on CPU
    pytest.param("hybrid", marks=pytest.mark.slow),
])
def tiny_cfg(request):
    return {
        "dense": TINY_DENSE, "moe": TINY_MOE,
        "ssm": TINY_SSM, "hybrid": TINY_HYBRID,
    }[request.param]


def pytest_configure(config):
    # Registered here as well as in pyproject.toml so `pytest path/to/test.py`
    # from any cwd never warns about unknown marks.
    config.addinivalue_line(
        "markers", "slow: long-running (benchmarks-adjacent) tests"
    )
    config.addinivalue_line(
        "markers", "chaos: chaos-engine scenario/replay tests (CI smoke job)"
    )
