"""Compile the main path for a described TPU v5e, without the chip.

The TPU compiler ships with libtpu and compiles for a topology that is
described, not attached.  These tests catch what interpret mode cannot: a
Pallas block the chip's tiling refuses, a step that does not fit 16 GB of
HBM, a sharding the 2x2 mesh cannot partition.  Nothing runs; only
shapes are compiled.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every test worker imports
this file.  The persistent compile cache stays off around these compiles.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.base import (
    MeCeFOConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
    get_config,
)
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.kernels import paged_decode as pd
from repro.launch.specs import input_specs, ndb_specs
from repro.launch.state import state_structs
from repro.launch.steps import build_rules, make_train_step

HBM_BYTES = 16e9  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("page_size", [16, 128])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, page_size):
    """qwen3-0.6b decode widths: 16 query heads, 8 KV heads, hd 128."""
    cfg = get_config("qwen3-0.6b")
    B, P, n_pages = 8, 8, 64
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pages = _struct((n_pages, page_size, KV, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(pd.paged_flash_decode).lower(
        _struct((B, 1, H, hd), jnp.bfloat16, one_chip), pages, pages,
        _struct((B, P), jnp.int32, one_chip),
        _struct((B,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_flash_decode_kernel_compiles_for_v5e(one_chip):
    """The dense kernel shares the paged kernel's body, so it must compile
    too."""
    cfg = get_config("qwen3-0.6b")
    B, S = 8, 1024
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cache = _struct((B, S, KV, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v, n: fd.flash_decode(q, k, v, n, block_k=512)
    ).lower(
        _struct((B, 1, H, hd), jnp.bfloat16, one_chip), cache, cache,
        _struct((B,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip):
    """Training attention at the qwen3-0.6b cell's shape (4 x 1024, 16
    heads over 8 KV heads, hd 128) with the TPU's tuned blocks: the
    forward and the fused backward kernel."""
    cfg = get_config("qwen3-0.6b")
    B, S = 4, 1024
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = ops.get_tuning("tpu")
    q = _struct((B, S, H, hd), jnp.bfloat16, one_chip)
    kv = _struct((B, S, KV, hd), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, block_q=t.attn_block_q, block_k=t.attn_block_k), q, k, v)
        return o, vjp(do)

    text = jax.jit(fwd_bwd).lower(q, kv, kv, q).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def _compile_train_step(devices, data):
    """The trainer's dynamic-NDB step for llama-350m at full width, cut to
    2 layers, on a (data, 1) mesh of described devices."""
    cfg = dataclasses.replace(get_config("llama-350m"), n_layers=2)
    shape = ShapeConfig("chip", 256, 16, "train")
    train = TrainConfig(steps=8)
    mecefo = MeCeFOConfig(mode="dynamic", rank=16, svd_period=20)
    parallel = ParallelConfig(fsdp=False, remat="ffn", scan_layers=True)
    mesh = Mesh(np.array(devices[:data]).reshape(data, 1), ("data", "model"))
    jitted, sshard, bshard, nshard = make_train_step(
        cfg, train, parallel, mecefo, mesh, shape, ndb_mode="dynamic",
        total_steps=train.steps, donate=False,
    )
    def place(structs, shardings):
        return jax.tree.map(
            lambda s, sh: _struct(s.shape, s.dtype, sh), structs, shardings
        )

    batch, _ = input_specs(
        cfg, shape, build_rules(cfg, mesh, parallel),
        {"data": data, "model": 1},
    )
    ndb, _ = ndb_specs(cfg, shape.global_batch, None)
    return jitted.lower(
        place(state_structs(cfg, train, mecefo), sshard),
        place(batch, bshard), place(ndb, nshard),
    ).compile()


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_train_step_compiles_for_one_v5e_chip(topo):
    compiled = _compile_train_step(topo.devices, data=1)
    assert 0 < _hbm_bytes(compiled) < HBM_BYTES


def test_train_step_compiles_for_v5e_2x2_data_parallel(topo):
    compiled = _compile_train_step(topo.devices, data=4)
    assert 0 < _hbm_bytes(compiled) < HBM_BYTES
    # replicated params, batch over `data`: the gradients are all-reduced
    assert "all-reduce" in compiled.as_text()
