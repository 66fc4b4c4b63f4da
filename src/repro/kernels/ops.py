"""jit'd public wrappers around the Pallas kernels — with backend-gated
implementation selection.

Every wrapper used to default to ``interpret=True``, which silently ran
the Pallas kernels through the Python interpreter on every backend — the
root cause of the `wall_speedup_paged: 0.29` upside-down perf story.  The
choice between *interpret*, *compiled Pallas*, and *compiled XLA
fallback* is now explicit, backend-derived, and logged once per wrapper:

* ``interpret=None`` (the default everywhere) resolves through
  :class:`KernelTuning` — on TPU the Pallas kernels compile natively, so
  interpret resolves ``False``; on the CPU (where the ``pltpu`` kernels
  have no compiled lowering) it resolves ``True`` for the dense kernels.
* The paged decode has a second compiled option: the pure-XLA
  page-table walk in ``kernels/xla_paged.py`` (bitwise-equal to the
  Pallas kernel).  :func:`resolve_paged_impl` picks ``"pallas"`` on TPU,
  ``"xla"`` elsewhere, and ``"pallas-interpret"`` only when interpret
  mode is explicitly requested.
* Training attention has two paths: the Pallas flash kernel with its
  backward, or the jnp einsum path of ``models/layers.causal_attention``.
  :func:`resolve_attention_impl` picks ``"flash"`` where the kernel
  applies (TPU, one device, tuned blocks dividing the sequence, lane-wide
  heads, no KV cache) and ``"jnp"`` everywhere else.
* Block sizes come from the per-backend :class:`KernelTuning` table and
  can be overridden with :func:`configure`.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import lowrank_wgrad as _lw
from repro.kernels import paged_decode as _pd
from repro.kernels import rmsnorm as _rn
from repro.kernels import swiglu as _sg
from repro.kernels import xla_paged as _xp
from repro.kernels import ref

_log = logging.getLogger("repro.kernels")

PAGED_IMPLS = ("pallas", "pallas-interpret", "xla")


@dataclasses.dataclass(frozen=True)
class KernelTuning:
    """Per-backend kernel selection + block-size table.

    ``interpret=None`` means backend-derived (compiled wherever a
    lowering exists); ``paged_impl=None`` likewise defers to
    :func:`resolve_paged_impl`.  Block sizes are the values the wrappers
    use when the caller passes ``None``.
    """
    interpret: Optional[bool] = None
    paged_impl: Optional[str] = None
    attn_block_q: int = 128
    attn_block_k: int = 128
    decode_block_k: int = 512
    wgrad_block_t: int = 256
    wgrad_block_m: int = 512
    swiglu_block_rows: int = 256
    swiglu_block_cols: int = 512
    rmsnorm_block_rows: int = 256

    def __post_init__(self):
        if self.paged_impl is not None and self.paged_impl not in PAGED_IMPLS:
            raise ValueError(
                f"paged_impl must be one of {PAGED_IMPLS}, got {self.paged_impl!r}"
            )


# The autotuning table: one entry per backend.  TPU keeps the larger MXU/
# VPU-aligned blocks (attention's from a sweep on a v5e chip, PERF.md); the
# CPU runs the dense kernels in interpret mode, so its block sizes matter
# mostly for tests.
_BACKEND_TUNING = {
    "tpu": KernelTuning(interpret=False, paged_impl="pallas",
                        attn_block_q=1024, attn_block_k=512),
    "cpu": KernelTuning(),
}
_tuning_override: Optional[KernelTuning] = None


def get_tuning(backend: Optional[str] = None) -> KernelTuning:
    if _tuning_override is not None:
        return _tuning_override
    backend = backend or jax.default_backend()
    return _BACKEND_TUNING.get(backend, KernelTuning())


def configure(tuning: Optional[KernelTuning]) -> None:
    """Install (or clear, with ``None``) a process-wide tuning override."""
    global _tuning_override
    _tuning_override = tuning
    _logged.clear()


def default_interpret(backend: Optional[str] = None) -> bool:
    """Backend-derived interpret default: compiled Pallas exists on TPU
    only; everywhere else the ``pltpu`` kernels must run interpreted."""
    backend = backend or jax.default_backend()
    return backend != "tpu"


def resolve_interpret(interpret: Optional[bool] = None,
                      backend: Optional[str] = None) -> bool:
    if interpret is not None:
        return interpret
    tuned = get_tuning(backend).interpret
    if tuned is not None:
        return tuned
    return default_interpret(backend)


def resolve_paged_impl(interpret: Optional[bool] = None,
                       backend: Optional[str] = None) -> str:
    """Pick the paged-decode implementation for this backend.

    ``interpret`` is the engine-level override knob
    (``EngineConfig.kernel_interpret``): ``True`` forces the interpret-
    mode Pallas kernel, ``False``/``None`` mean "compiled" — the Pallas
    kernel on TPU, the bitwise-equal XLA page walk everywhere else.
    """
    backend = backend or jax.default_backend()
    if interpret:
        return "pallas-interpret"
    tuned = get_tuning(backend).paged_impl
    if tuned is not None and not (tuned == "pallas" and backend != "tpu"):
        return tuned
    return "pallas" if backend == "tpu" else "xla"


# The flash backward keeps the whole q/dO sequence of one (batch, KV head)
# in VMEM, double-buffered, with dQ's f32 accumulator and output block.
ATTN_RESIDENT_VMEM_BYTES = 32 * 1024 * 1024


def resolve_attention_impl(q_shape, n_kv_heads: int, *, cached: bool = False,
                           backend: Optional[str] = None,
                           n_devices: Optional[int] = None) -> str:
    """``"flash"`` where causal attention of queries ``q_shape`` (B, S, H,
    hd) can take the Pallas flash kernel, else ``"jnp"``.

    The kernel takes it on TPU, without a KV cache (training; prefill
    keeps the jnp path), on one device (a trace cannot see whether its
    inputs are sharded, and GSPMD partitions the jnp path but not a
    kernel), with ``head_dim`` a multiple of 128 lanes, the tuned grid
    block (shrunk to S) dividing S and its tile a multiple of 128 dividing
    the block, and the backward's resident sequence within VMEM.
    """
    backend = backend or jax.default_backend()
    n_devices = jax.device_count() if n_devices is None else n_devices
    _, S, H, hd = q_shape
    t = get_tuning(backend)
    bq = min(t.attn_block_q, S)
    tile = min(t.attn_block_k, bq)
    resident = 16 * S * (H // n_kv_heads) * hd
    ok = (
        backend == "tpu" and not cached and n_devices == 1
        and hd % 128 == 0 and H % n_kv_heads == 0
        and tile % 128 == 0 and bq % tile == 0 and S % bq == 0
        and resident <= ATTN_RESIDENT_VMEM_BYTES
    )
    return "flash" if ok else "jnp"


_logged: set = set()
_impl_counters: dict = {}


def _log_choice(name: str, impl: str) -> None:
    """Record one kernel dispatch under its resolved implementation:
    a ``kernels.impl_calls{kernel,impl}`` count per call, an INFO log
    line once per (kernel, impl) pair."""
    from repro import obs

    with obs.span("kernel.select"):
        key = (name, impl)
        counter = _impl_counters.get(key)
        if counter is None:
            counter = _impl_counters[key] = obs.counter(
                "kernels.impl_calls", labels={"kernel": name, "impl": impl}
            )
        counter.inc()
        if key not in _logged:
            _logged.add(key)
            _log.info(
                "kernel %s -> %s (backend=%s)",
                name, impl, jax.default_backend(),
            )


def count_attention_sites(impl: str, sites: int = 1) -> None:
    """Record, at trace time, that ``sites`` attention layers compiled onto
    ``impl`` (``kernels.attention_sites{impl}``; a scanned layer stack is
    one traced site standing for each of its layers)."""
    from repro import obs

    _log_choice("attention", impl)
    obs.counter("kernels.attention_sites", labels={"impl": impl}).inc(sites)


def _pad_to(x, axis: int, multiple: int):
    """Zero-pad ``axis`` up to the next multiple (hardware-aligned blocks
    stay intact; padding is handled here at the wrapper, not in-kernel)."""
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, multiple - rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _flash_attention_jit(q, k, v, *, causal, block_q, block_k, interpret):
    return _fa.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def flash_attention(q, k, v, *, causal=True, block_q=None, block_k=None,
                    interpret=None):
    t = get_tuning()
    block_q = t.attn_block_q if block_q is None else block_q
    block_k = t.attn_block_k if block_k is None else block_k
    interpret = resolve_interpret(interpret)
    _log_choice("flash_attention", "pallas-interpret" if interpret else "pallas")
    return _flash_attention_jit(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def _flash_decode_jit(q, k_cache, v_cache, cur_len, *, block_k, interpret):
    # ragged caches: pad Smax to a block multiple; padded positions sit past
    # cur_len (<= the original Smax) so the kernel's length mask drops them
    Smax = k_cache.shape[1]
    bk = min(block_k, Smax)
    k_cache = _pad_to(k_cache, 1, bk)
    v_cache = _pad_to(v_cache, 1, bk)
    return _fd.flash_decode(
        q, k_cache, v_cache, cur_len, block_k=block_k, interpret=interpret
    )


def flash_decode(q, k_cache, v_cache, cur_len, *, block_k=None, interpret=None):
    block_k = get_tuning().decode_block_k if block_k is None else block_k
    interpret = resolve_interpret(interpret)
    _log_choice("flash_decode", "pallas-interpret" if interpret else "pallas")
    return _flash_decode_jit(
        q, k_cache, v_cache, cur_len, block_k=block_k, interpret=interpret
    )


def paged_dispatch(q, k_pages, v_pages, tables, cur_len, *, impl=None,
                   k_scale=None, v_scale=None):
    """Route one paged-decode call to its implementation.

    Plain (non-jitted) so it can be called from inside other jits
    (``models/layers.py``).  ``impl=None`` resolves backend-derived.
    int8 pools (``k_scale``/``v_scale`` set) are XLA-only — the Pallas
    kernel has no sub-(32, 128)-tile int8 lowering (see the Pallas guide
    tiling table), so quantized pages always take the compiled walk.
    """
    if impl is None:
        impl = resolve_paged_impl()
    if k_scale is not None or v_scale is not None:
        if impl != "xla":
            raise ValueError(f"int8 KV pages require impl='xla', got {impl!r}")
        return _xp.paged_flash_decode_xla(
            q, k_pages, v_pages, tables, cur_len,
            k_scale=k_scale, v_scale=v_scale,
        )
    if impl == "xla":
        return _xp.paged_flash_decode_xla(q, k_pages, v_pages, tables, cur_len)
    return _pd.paged_flash_decode(
        q, k_pages, v_pages, tables, cur_len,
        interpret=(impl == "pallas-interpret"),
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def _paged_flash_decode_jit(q, k_pages, v_pages, tables, cur_len, k_scale,
                            v_scale, *, impl):
    return paged_dispatch(
        q, k_pages, v_pages, tables, cur_len, impl=impl,
        k_scale=k_scale, v_scale=v_scale,
    )


def paged_flash_decode(q, k_pages, v_pages, tables, cur_len, *,
                       interpret=None, impl=None, k_scale=None, v_scale=None):
    """Page-table-walking flash decode over the physical KV pool.

    Bitwise-identical to ``flash_decode(q, gather(k_pages, tables),
    gather(v_pages, tables), cur_len, block_k=page_size)`` under every
    implementation — the zero-copy serving decode path (see
    kernels/paged_decode.py and kernels/xla_paged.py).
    """
    if impl is None:
        impl = resolve_paged_impl(interpret)
    _log_choice("paged_flash_decode", impl)
    return _paged_flash_decode_jit(
        q, k_pages, v_pages, tables, cur_len, k_scale, v_scale, impl=impl
    )


@functools.partial(jax.jit, static_argnames=("block_t", "block_m", "interpret"))
def _lowrank_wgrad_jit(x, dy, v1, *, block_t, block_m, interpret):
    T, m = x.shape[0], dy.shape[1]
    bt, bm = min(block_t, T), min(block_m, m)
    x = _pad_to(x, 0, bt)
    dy = _pad_to(_pad_to(dy, 0, bt), 1, bm)
    a = _lw.lowrank_wgrad_project(
        x, dy, v1, block_t=block_t, block_m=block_m, interpret=interpret
    )[:, :m]
    return (v1.astype(jnp.float32) @ a).astype(v1.dtype)


def lowrank_wgrad(x, dy, v1, *, block_t=None, block_m=None, interpret=None):
    """Full technique-III Wgrad: dW = v1 @ ((x v1)^T dy).

    Odd (non-block-multiple) T and m are zero-padded up to the block grid:
    zero token rows contribute nothing to the accumulator and the padded
    output columns are sliced off, so the result is exact.
    """
    t = get_tuning()
    block_t = t.wgrad_block_t if block_t is None else block_t
    block_m = t.wgrad_block_m if block_m is None else block_m
    interpret = resolve_interpret(interpret)
    _log_choice("lowrank_wgrad", "pallas-interpret" if interpret else "pallas")
    return _lowrank_wgrad_jit(
        x, dy, v1, block_t=block_t, block_m=block_m, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def _swiglu_jit(g, u, *, block_rows, block_cols, interpret):
    return _sg.swiglu(
        g, u, block_rows=block_rows, block_cols=block_cols, interpret=interpret
    )


def swiglu(g, u, *, block_rows=None, block_cols=None, interpret=None):
    t = get_tuning()
    block_rows = t.swiglu_block_rows if block_rows is None else block_rows
    block_cols = t.swiglu_block_cols if block_cols is None else block_cols
    interpret = resolve_interpret(interpret)
    _log_choice("swiglu", "pallas-interpret" if interpret else "pallas")
    return _swiglu_jit(
        g, u, block_rows=block_rows, block_cols=block_cols, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm_jit(x, scale, eps, *, block_rows, interpret):
    return _rn.rmsnorm(x, scale, eps, block_rows=block_rows, interpret=interpret)


def rmsnorm(x, scale, eps=1e-5, *, block_rows=None, interpret=None):
    block_rows = get_tuning().rmsnorm_block_rows if block_rows is None else block_rows
    interpret = resolve_interpret(interpret)
    _log_choice("rmsnorm", "pallas-interpret" if interpret else "pallas")
    return _rmsnorm_jit(
        x, scale, eps, block_rows=block_rows, interpret=interpret
    )


__all__ = [
    "flash_attention", "flash_decode", "paged_flash_decode", "paged_dispatch",
    "lowrank_wgrad", "swiglu", "rmsnorm", "ref",
    "KernelTuning", "get_tuning", "configure",
    "default_interpret", "resolve_interpret", "resolve_paged_impl",
    "resolve_attention_impl", "count_attention_sites",
]
