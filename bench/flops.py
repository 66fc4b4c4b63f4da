"""Operations and bytes of the model's work, counted from its sizes.

The model-FLOP convention is the one of ``repro.launch.roofline.model_flops``
(copied): a matmul of an ``n x m`` weight costs ``2 n m`` per token forward
and ``6 n m`` forward and backward; causal attention costs ``4 H hd`` per
query and key pair forward, half of the pairs counted.  Recomputed work is
not counted.  ``m`` is the dict of sizes that ``bench.cell.model_sizes``
reads from a configuration file.
"""
from __future__ import annotations

from typing import Sequence

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_matmul_params(m: dict) -> int:
    d, H, KV, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def matmul_params(m: dict) -> int:
    """Weights that take part in a matmul for every token (the embedding
    lookup is a gather; the head is a matmul, tied or not)."""
    return m["n_layers"] * layer_matmul_params(m) + head_params(m)


def weight_bytes(m: dict) -> int:
    """Bytes of every weight a decode round reads: the layers and the head
    (the embedding table is gathered row by row)."""
    d, L = m["d_model"], m["n_layers"]
    norms = L * 2 * d + d + (2 * L * m["head_dim"] if m["qk_norm"] else 0)
    return (matmul_params(m) + norms) * DTYPE_BYTES[m["dtype"]]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    attn_fwd = 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq_len * 0.5
    return 6.0 * matmul_params(m) + 3.0 * attn_fwd


def kv_bytes_per_token(m: dict) -> int:
    return (m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[m["dtype"]])


def pages(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def decode_round_flops(m: dict, ctx: Sequence[int]) -> float:
    """One decode round: each active lane reads ``ctx[i]`` cached positions
    plus its own new one."""
    L, H, hd = m["n_layers"], m["n_heads"], m["head_dim"]
    return sum(2.0 * matmul_params(m) + 4.0 * L * H * hd * (c + 1) for c in ctx)


def decode_round_bytes(m: dict, ctx: Sequence[int], page_size: int) -> float:
    """Weights once, plus the live KV pages of every active lane."""
    per_page = kv_bytes_per_token(m) * page_size
    return weight_bytes(m) + per_page * sum(pages(c + 1, page_size) for c in ctx)


def paged_kernel_bytes(m: dict, ctx: Sequence[int], page_size: int) -> float:
    """Least bytes the paged decode kernel reads and writes in one round,
    over all layers: live K and V pages, q and the output of each lane."""
    b = DTYPE_BYTES[m["dtype"]]
    per_page = kv_bytes_per_token(m) * page_size
    qo = 2 * m["n_layers"] * m["n_heads"] * m["head_dim"] * b
    return sum(per_page * pages(c + 1, page_size) + qo for c in ctx)
