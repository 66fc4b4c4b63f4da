"""Plain float32 reference of the dense GQA SwiGLU decoder, written from the
layer equations and independent of the program under test.

It imports nothing of the program.  Weights are made from the seed with the
same arithmetic as the program's initialiser (fan-in scaled normals, unit
norm scales, one key per leaf in the order of the flattened tree), stored in
the configuration's dtype, and computed on in float32 at the highest matmul
precision.

Equations, per layer ``l`` with input ``h``:

    a = rmsnorm(h) ; q, k, v = a Wq, a Wk, a Wv ; (rmsnorm of q and k per head
    when ``qk_norm``) ; rotary on q and k (rotate-half, all of the head dim)
    h = h + gate_l(causal_softmax(q k^T / sqrt(hd)) v Wo)
    b = rmsnorm(h) ; h = h + (silu(b Wg) * (b Wu)) Wd

then ``logits = rmsnorm(h) E^T`` (tied) or ``rmsnorm(h) U``.

MeCeFO (training, dynamic neighbour-do-both masks ``keep[l, b]``):

* ``gate_l`` is the identity forward; its backward multiplies the cotangent
  of example ``b`` by ``keep[l, b]`` (skip-MHA backward);
* the FFN weight gradients of examples with ``keep = 0`` are projected on
  the top-``r`` left singular subspace ``V`` of the weight:
  ``dW = x^T (k dy) + V V^T x^T ((1 - k) dy)``; ``dx`` stays exact;
* MHA weight gradients of layer ``l`` are scaled by ``n / |N_l|`` (eq. 1);
* ``V`` starts at zero and is refreshed from the weights after every step
  whose index is a multiple of the SVD period.

``quant="fp8"`` rounds every matmul's operands to float8 e4m3 values (a
scale per row of the contraction) before the product: the control that
stands for a program one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("ln", "final_norm", "q_norm", "k_norm")


def accept(m: dict) -> None:
    """Raises for sizes this reference does not compute: experts, SSD
    layers, a family other than dense, an activation other than SwiGLU."""
    other = [k for k in sorted(m) if k.startswith(("moe.", "ssm."))]
    other += [k for k, ours in (("family", "dense"), ("ffn_act", "swiglu"))
              if m.get(k, ours) != ours]
    if other:
        raise ValueError("the reference covers dense SwiGLU only, not "
                         + ", ".join(f"{k}={m[k]!r}" for k in other))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def leaf_shapes(m: dict) -> dict:
    """The parameter tree as nested dicts of shape tuples (layers stacked)."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    H, KV, hd, F = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    mixer = {"ln": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
             "wv": (L, d, KV * hd), "wo": (L, H * hd, d)}
    if m["qk_norm"]:
        mixer["q_norm"] = (L, hd)
        mixer["k_norm"] = (L, hd)
    ffn = {"ln": (L, d), "w_gate": (L, d, F), "w_up": (L, d, F),
           "w_down": (L, F, d)}
    tree = {"embed": (V, d), "final_norm": (d,),
            "layers": ({"ffn": ffn, "mixer": mixer},)}
    if not m["tie_embeddings"]:
        tree["unembed"] = (d, V)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def flat_shapes(m: dict):
    """[(name, shape)] in the order of the flattened tree; ``name`` is the
    path joined by ``/`` (``layers/0/ffn/w_up``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(leaf_shapes(m),
                                                   is_leaf=_is_shape)
    out = []
    for path, shape in flat:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        out.append(("/".join(keys), shape))
    return out


def _init_leaf(name: str, shape, key, dtype):
    leaf = name.split("/")[-1]
    if leaf == "embed":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
    if leaf in NORMS:
        return jnp.ones(shape, dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(max(fan_in, 1))).astype(dtype)


def init_weights(m: dict, seed: int) -> Dict[str, jax.Array]:
    """``{name: array}`` in the configuration's dtype, in one jitted call."""
    names = flat_shapes(m)
    dtype = jnp.dtype(m["dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        return {n: _init_leaf(n, s, k, dtype)
                for (n, s), k in zip(names, keys)}

    return make(jax.random.PRNGKey(seed))


def layer_tree(w: dict) -> dict:
    """The per-layer leaves, stacked, keyed ``ffn/w_up`` etc."""
    return {k[len("layers/0/"):]: v for k, v in w.items()
            if k.startswith("layers/0/")}


# ---------------------------------------------------------------------------
# layer equations
# ---------------------------------------------------------------------------


def _fp8(x, axis):
    """float8 e4m3 values (3 mantissa bits, largest 448) with a scale per
    row of the contraction, emulated in float32; straight-through."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    y = x / s
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 1e-30))), -6.0)
    step = jnp.exp2(e - 3.0)
    q = jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, quant=None):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    if quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA attention. q: (B, S, H, hd); k, v: (B, S, KV, hd)."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


@jax.custom_vjp
def gate(y, keep):
    return y


def _gate_fwd(y, keep):
    return y, keep


def _gate_bwd(keep, dy):
    return dy * keep[:, None, None], jnp.zeros_like(keep)


gate.defvjp(_gate_fwd, _gate_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def mecefo_linear(x, w, v, keep, quant):
    return mm(x, w, quant)


def _ml_fwd(x, w, v, keep, quant):
    return mm(x, w, quant), (x, w, v, keep)


def _ml_bwd(quant, res, dy):
    x, w, v, keep = res
    k = keep[:, None, None]
    dx = jnp.einsum("bsm,nm->bsn", dy, w, precision=HIGHEST)
    exact = jnp.einsum("bsn,bsm->nm", x, dy * k, precision=HIGHEST)
    xv = jnp.einsum("bsn,nr->bsr", x, v, precision=HIGHEST)
    low = jnp.einsum("bsr,bsm->rm", xv, dy * (1 - k), precision=HIGHEST)
    return dx, exact + jnp.matmul(v, low, precision=HIGHEST), \
        jnp.zeros_like(v), jnp.zeros_like(keep)


mecefo_linear.defvjp(_ml_fwd, _ml_bwd)


def block(m, lp, h, positions, keep=None, proj=None, quant=None):
    """One decoder layer on f32 values; ``keep`` (B,) and ``proj`` select the
    MeCeFO backward."""
    B, S, _ = h.shape
    H, KV, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    a = rmsnorm(h, lp["mixer/ln"], eps)
    q = mm(a, lp["mixer/wq"], quant).reshape(B, S, H, hd)
    k = mm(a, lp["mixer/wk"], quant).reshape(B, S, KV, hd)
    v = mm(a, lp["mixer/wv"], quant).reshape(B, S, KV, hd)
    if m["qk_norm"]:
        q = rmsnorm(q, lp["mixer/q_norm"], eps)
        k = rmsnorm(k, lp["mixer/k_norm"], eps)
    q = rope(q, positions, m["rope_theta"])
    k = rope(k, positions, m["rope_theta"])
    y = mm(attention(q, k, v).reshape(B, S, H * hd), lp["mixer/wo"], quant)
    if keep is not None:
        y = gate(y, keep)
    h = h + y
    b = rmsnorm(h, lp["ffn/ln"], eps)
    if keep is None:
        lin = lambda x, name: mm(x, lp[f"ffn/{name}"], quant)  # noqa: E731
    else:
        lin = lambda x, name: mecefo_linear(  # noqa: E731
            x, lp[f"ffn/{name}"], proj[name], keep, quant)
    act = jax.nn.silu(lin(b, "w_gate")) * lin(b, "w_up")
    return h + lin(act, "w_down")


def trunk(m, w32, tokens, keep=None, proj=None, quant=None, remat=True):
    """Embedding and every layer; returns the final-normed hidden states."""
    positions = jnp.arange(tokens.shape[1])
    h = w32["embed"][tokens]
    layers = layer_tree(w32)

    def body(h, xs):
        lp, kp, pj = xs
        return block(m, lp, h, positions, kp, pj, quant), None

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, (layers, keep, proj))
    return rmsnorm(h, w32["final_norm"], m["norm_eps"])


def head(w32):
    return w32["unembed"] if "unembed" in w32 else w32["embed"].T


def upcast(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


# ---------------------------------------------------------------------------
# training: loss, MeCeFO gradient, AdamW, SVD refresh
# ---------------------------------------------------------------------------


def chunked_nll(h, unembed, labels, weight, quant=None, chunk=256):
    """Token-weighted mean cross-entropy without the whole (B, S, V)."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    hc = h.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    yc = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(hx, yx):
        logits = mm(hx, unembed, quant)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yx[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * weight[:, None])

    tot = jax.lax.map(lambda xs: one(*xs), (hc, yc)).sum()
    return tot / jnp.maximum(jnp.sum(weight) * S, 1.0)


def lr_at(job: dict, step: int) -> float:
    """Linear warm-up then cosine decay to a tenth (the program's schedule,
    written out)."""
    total = max(job["total_steps"], 1)
    warm = max(int(total * job["warmup_frac"]), 1)
    lr = job["learning_rate"]
    if step < warm:
        return lr * step / warm
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.45 * (1 + math.cos(math.pi * prog)))


def leaf_norms(tree: dict) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_train_step(m: dict, job: dict, quant=None):
    """One optimizer step of the reference: returns a jitted
    ``(w, mu, nu, proj, tokens, labels, keep, weight, lr, t) ->
    (w, mu, nu, loss, {name: |clipped grad|})``."""
    b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
    mha = [k for k in flat_names(m) if k.startswith("layers/0/mixer/")]

    def loss_fn(w32, proj, tokens, labels, keep, weight):
        h = trunk(m, w32, tokens, keep, proj, quant)
        return chunked_nll(h, head(w32), labels, weight, quant)

    def step(w, mu, nu, proj, tokens, labels, keep, weight, lr, t):
        w32 = upcast(w)
        loss, g = jax.value_and_grad(loss_fn)(w32, proj, tokens, labels,
                                              keep, weight)
        if keep is not None:  # eq. (1): MHA gradients over the live share
            live = jnp.maximum(jnp.sum(weight), 1e-8)
            frac = jnp.sum(keep * weight[None, :], axis=1) / live
            f = jnp.where(frac > 0, 1.0 / jnp.maximum(frac, 1e-8), 0.0)
            for k in mha:
                g[k] = g[k] * f.reshape((-1,) + (1,) * (g[k].ndim - 1))
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        scale = jnp.minimum(1.0, job["grad_clip"] / jnp.maximum(gn, 1e-12))
        g = {k: x * scale for k, x in g.items()}
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * nu[k] + (1 - b2) * g[k] * g[k] for k in g}
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        new_w = {}
        for k in w:
            upd = (mu[k] / bc1) / (jnp.sqrt(nu[k] / bc2) + eps) + wd * w32[k]
            new_w[k] = (w32[k] - lr * upd).astype(w[k].dtype)
        return new_w, mu, nu, loss, leaf_norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def flat_names(m: dict) -> List[str]:
    return [n for n, _ in flat_shapes(m)]


def zero_proj(m: dict, rank: int) -> Dict[str, jax.Array]:
    L, d, F = m["n_layers"], m["d_model"], m["d_ff"]
    return {"w_gate": jnp.zeros((L, d, rank)), "w_up": jnp.zeros((L, d, rank)),
            "w_down": jnp.zeros((L, F, rank))}


@partial(jax.jit, static_argnums=(1,))
def _top_left(w, rank):
    u, _, _ = jnp.linalg.svd(w.astype(jnp.float32), full_matrices=False)
    return u[..., :rank]


def svd_proj(w: dict, rank: int) -> Dict[str, jax.Array]:
    """Top-``rank`` left singular vectors (input space) of each FFN weight,
    one program per weight shape."""
    return {name: _top_left(w[f"layers/0/ffn/{name}"], rank)
            for name in ("w_gate", "w_up", "w_down")}


def run_training(m: dict, job: dict, seed: int, batches: Sequence[dict],
                 keep: Optional[np.ndarray], weight: np.ndarray,
                 quant=None) -> dict:
    """The reference's first ``len(batches)`` steps.  Returns the loss of
    each step, the per-leaf norm of the first step's clipped gradient, and
    the per-leaf norm of the weights' change over all the steps."""
    w = init_weights(m, seed)
    w0 = {k: np.asarray(v.astype(jnp.float32)) for k, v in w.items()}
    mu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in w.items()}
    nu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in w.items()}
    proj = zero_proj(m, job["rank"]) if keep is not None else None
    step = make_train_step(m, job, quant)
    keep_j = None if keep is None else jnp.asarray(keep, jnp.float32)
    weight_j = jnp.asarray(weight, jnp.float32)
    losses, grad0 = [], None
    for t, b in enumerate(batches):
        w, mu, nu, loss, gn = step(
            w, mu, nu, proj, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
            keep_j, weight_j, jnp.float32(lr_at(job, t)), jnp.float32(t + 1))
        losses.append(float(loss))
        if grad0 is None:
            grad0 = {k: float(v) for k, v in gn.items()}
        if keep is not None and t % job["svd_period"] == 0:
            proj = svd_proj(w, job["rank"])
    delta = {k: float(np.linalg.norm(np.asarray(v.astype(jnp.float32)) - w0[k]))
             for k, v in w.items()}
    return {"losses": losses, "grad0": grad0, "delta": delta}


# ---------------------------------------------------------------------------
# serving: logits along a whole sequence
# ---------------------------------------------------------------------------


def make_seq_logits(m: dict, quant=None):
    """jitted ``(w, tokens (1, T), start, n) -> (n, V)`` f32 logits at
    positions ``start .. start + n - 1`` of a padded sequence (causal: the
    padding after a position never reaches it)."""

    @partial(jax.jit, static_argnums=(3,))
    def f(w, tokens, start, n):
        positions = jnp.arange(tokens.shape[1])
        h = w["embed"][tokens].astype(jnp.float32)

        def body(h, lp):  # one layer's weights in f32 at a time
            lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
            return block(m, lp, h, positions, quant=quant), None

        h, _ = jax.lax.scan(body, h, layer_tree(w))
        h = rmsnorm(h, w["final_norm"].astype(jnp.float32), m["norm_eps"])
        hs = jax.lax.dynamic_slice_in_dim(h[0], start, n, axis=0)
        return mm(hs, head(w).astype(jnp.float32), quant)

    return f
