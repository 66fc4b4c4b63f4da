"""Operations and bytes of the model's work, counted from its sizes.

The model-FLOP convention is the one of ``repro.launch.roofline.model_flops``
(copied): a matmul of an ``n x m`` weight costs ``2 n m`` per token forward
and ``6 n m`` forward and backward; causal attention costs ``4 H hd`` per
query and key pair forward, half of the pairs counted; an SSD (Mamba-2)
layer adds its chunked scan.  Recomputed work is not counted.  ``m`` is the
dict of sizes that ``bench.cell.model_sizes`` reads from a configuration
file.

Each layer is counted by its kind, with the program's rules: ``family``,
``attn_every`` and ``attn_offset`` make it an attention or an SSD layer;
``moe.every`` and ``moe.offset`` make its FFN an expert layer where the map
has ``moe.*`` sizes.  A map without these keys is a dense decoder.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_kind(m: dict, layer: int) -> str:
    """``"attn"`` or ``"ssm"``: the mixer of layer ``layer``."""
    family = m.get("family", "dense")
    if family == "ssm":
        return "ssm"
    if family == "hybrid":
        hit = layer % m.get("attn_every", 1) == m.get("attn_offset", 0)
        return "attn" if hit else "ssm"
    return "attn"


def layer_is_moe(m: dict, layer: int) -> bool:
    if not any(k.startswith("moe.") for k in m):
        return False
    return layer % m.get("moe.every", 1) == m.get("moe.offset", 0)


def layer_pattern(m: dict) -> List[Tuple[str, bool]]:
    """``[(mixer kind, expert FFN), ...]``, one entry a layer."""
    return [(layer_kind(m, i), layer_is_moe(m, i))
            for i in range(m["n_layers"])]


def missing_sizes(m: dict) -> List[str]:
    """The sizes the count of ``m``'s expert and SSD layers reads that the
    map does not state."""
    pattern = layer_pattern(m)
    need = []
    if any(moe for _, moe in pattern):
        need += ["moe.n_experts", "moe.top_k", "moe.d_ff_expert"]
    if any(kind == "ssm" for kind, _ in pattern):
        need += ["ssm.d_state", "ssm.expand", "ssm.head_dim", "ssm.chunk"]
    return [k for k in need if k not in m]


def n_attn_layers(m: dict) -> int:
    return sum(1 for kind, _ in layer_pattern(m) if kind == "attn")


def attn_params(m: dict) -> int:
    d, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d


def ssd_inner(m: dict) -> Tuple[int, int]:
    """(inner width, SSD heads) of an SSD layer."""
    d_inner = m["ssm.expand"] * m["d_model"]
    return d_inner, d_inner // m["ssm.head_dim"]


def ssd_params(m: dict) -> int:
    """The in-projection to z, x, B, C and dt, and the out-projection."""
    d = m["d_model"]
    d_inner, nh = ssd_inner(m)
    return d * (2 * d_inner + 2 * m["ssm.d_state"] + nh) + d_inner * d


def ffn_matrices(m: dict) -> int:
    """Three for a gated FFN (SwiGLU), two for ``gelu`` and ``relu2``."""
    return 3 if m.get("ffn_act", "swiglu") == "swiglu" else 2


def ffn_params(m: dict, expert: bool) -> int:
    """An expert layer's ``top_k`` experts and its router, or a dense FFN."""
    d = m["d_model"]
    if expert:
        return (m["moe.top_k"] * ffn_matrices(m) * d * m["moe.d_ff_expert"]
                + d * m["moe.n_experts"])
    return ffn_matrices(m) * d * m["d_ff"]


def layer_matmul_params(m: dict, layer: int = 0) -> int:
    """Weights of layer ``layer`` that take part in a matmul for a token."""
    mixer = attn_params(m) if layer_kind(m, layer) == "attn" else ssd_params(m)
    return mixer + ffn_params(m, layer_is_moe(m, layer))


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def matmul_params(m: dict) -> int:
    """Weights that take part in a matmul for every token (the embedding
    lookup is a gather; the head is a matmul, tied or not; of an expert
    layer, the experts a token is routed to)."""
    return (sum(layer_matmul_params(m, i) for i in range(m["n_layers"]))
            + head_params(m))


def _decode_counted(m: dict) -> None:
    """A decode round is counted for attention layers with dense FFNs only:
    which experts a round reads, and what an SSD layer's decode step reads
    and computes, are for the change that serves such a model to count."""
    other = sorted({p for p in layer_pattern(m) if p != ("attn", False)})
    if other:
        raise ValueError(f"decode rounds are counted for dense attention "
                         f"layers only, not {other}")


def weight_bytes(m: dict) -> int:
    """Bytes of every weight a decode round reads: the layers and the head
    (the embedding table is gathered row by row)."""
    _decode_counted(m)
    d, L = m["d_model"], m["n_layers"]
    norms = L * 2 * d + d + (2 * L * m["head_dim"] if m["qk_norm"] else 0)
    return (matmul_params(m) + norms) * DTYPE_BYTES[m["dtype"]]


def ssd_scan_flops_per_token(m: dict) -> float:
    """Forward FLOPs of the chunked scan of every SSD layer, per token:
    the intra-chunk products (causal, half counted), the chunk states and
    their readout."""
    n_ssm = m["n_layers"] - n_attn_layers(m)
    if not n_ssm:
        return 0.0
    d_inner, nh = ssd_inner(m)
    Q, N, P = m["ssm.chunk"], m["ssm.d_state"], m["ssm.head_dim"]
    per_tok = (2 * Q * N + 2 * Q * nh * P) * 0.5
    per_tok += 4 * N * d_inner
    return n_ssm * per_tok


def attn_pair_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward FLOPs of the causal score and value products of every
    attention layer, per token."""
    n = n_attn_layers(m)
    return 4.0 * n * m["n_heads"] * m["head_dim"] * seq_len * 0.5 if n else 0.0


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return (6.0 * matmul_params(m) + 3.0 * attn_pair_flops_per_token(m, seq_len)
            + 3.0 * ssd_scan_flops_per_token(m))


def kv_bytes_per_token(m: dict) -> int:
    return (n_attn_layers(m) * 2 * m["n_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[m["dtype"]])


def pages(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


def decode_round_flops(m: dict, ctx: Sequence[int]) -> float:
    """One decode round: each active lane reads ``ctx[i]`` cached positions
    plus its own new one."""
    _decode_counted(m)
    L, H, hd = m["n_layers"], m["n_heads"], m["head_dim"]
    return sum(2.0 * matmul_params(m) + 4.0 * L * H * hd * (c + 1) for c in ctx)


def decode_round_bytes(m: dict, ctx: Sequence[int], page_size: int) -> float:
    """Weights once, plus the live KV pages of every active lane."""
    per_page = kv_bytes_per_token(m) * page_size
    return weight_bytes(m) + per_page * sum(pages(c + 1, page_size) for c in ctx)


def paged_kernel_bytes(m: dict, ctx: Sequence[int], page_size: int) -> float:
    """Least bytes the paged decode kernel reads and writes in one round,
    over all attention layers: live K and V pages, q and the output of each
    lane."""
    b = DTYPE_BYTES[m["dtype"]]
    per_page = kv_bytes_per_token(m) * page_size
    qo = 2 * n_attn_layers(m) * m["n_heads"] * m["head_dim"] * b
    return sum(per_page * pages(c + 1, page_size) + qo for c in ctx)
