"""Operations and bytes the model's mathematics needs, from the
configuration file alone.

These count the work as the configuration defines it, not what a kernel
executes: no padded slots, no padded chunk tails, no recomputation in the
backward pass, no float32 copies of bf16 operands. So a roofline or MFU
reads the same work whatever implements it. A butterfly over ``n`` inputs
is defined on the padded power of two ``N`` (the paper's footnote 4) and
costs ``3 N`` operations per stage (``a·x + b·x'``); a truncation is a
selection and costs nothing.
"""

from __future__ import annotations

import math
from typing import Dict

BF16 = 2


def padded(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def default_k(n: int, k_factor: float) -> int:
    return min(max(1, int(round(k_factor * math.log2(max(n, 2))))), n)


def _is_butterfly(mc: Dict, site: str) -> bool:
    b = mc.get("butterfly")
    return bool(b) and site in b["sites"]


def sandwich_flops(n_in: int, n_out: int, k_factor: float) -> int:
    """Forward operations of one sandwich row: butterfly in, core,
    transposed butterfly out."""
    N1, N2 = padded(n_in), padded(n_out)
    k1, k2 = default_k(n_in, k_factor), default_k(n_out, k_factor)
    return (3 * N1 * int(math.log2(N1)) + 2 * k1 * k2
            + 3 * N2 * int(math.log2(N2)))


def sandwich_weights(n_in: int, n_out: int, k_factor: float) -> int:
    N1, N2 = padded(n_in), padded(n_out)
    k1, k2 = default_k(n_in, k_factor), default_k(n_out, k_factor)
    return (2 * N1 * int(math.log2(N1)) + k1 * k2
            + 2 * N2 * int(math.log2(N2)))


def projections(mc: Dict):
    """(site, n_in, n_out, count per token) of every projection."""
    E, F, V = mc["hidden_size"], mc["intermediate_size"], mc["vocab_size"]
    H, KV, D = (mc["num_attention_heads"], mc["num_key_value_heads"],
                mc["head_dim"])
    L = mc["num_hidden_layers"]
    out = [("qkv", E, (H + 2 * KV) * D, L), ("attn_out", H * D, E, L),
           ("mlp", E, F, 2 * L), ("mlp", F, E, L)]
    out.append(("lm_head", E, V, 1))
    return out


def site_flops(mc: Dict, site: str, n_in: int, n_out: int) -> int:
    if _is_butterfly(mc, site):
        return sandwich_flops(n_in, n_out, mc["butterfly"]["k_factor"])
    return 2 * n_in * n_out


def site_weights(mc: Dict, site: str, n_in: int, n_out: int) -> int:
    if _is_butterfly(mc, site):
        return sandwich_weights(n_in, n_out, mc["butterfly"]["k_factor"])
    if site == "lm_head" and mc["tie_word_embeddings"]:
        return 0            # the embedding table, counted once below
    return n_in * n_out


def weight_count(mc: Dict) -> int:
    n = sum(c * site_weights(mc, s, i, o) for s, i, o, c in projections(mc))
    return n + mc["vocab_size"] * mc["hidden_size"]


def forward_flops_per_token(mc: Dict, context: float) -> float:
    """One token's forward pass attending to ``context`` positions."""
    f = sum(c * site_flops(mc, s, i, o) for s, i, o, c in projections(mc))
    attn = (4 * mc["num_attention_heads"] * mc["head_dim"] * context
            * mc["num_hidden_layers"])
    return float(f + attn)


def kv_bytes_per_position(mc: Dict) -> int:
    return (2 * mc["num_hidden_layers"] * mc["num_key_value_heads"]
            * mc["head_dim"] * BF16)




def train_flops_per_token(mc: Dict, seq: int) -> float:
    """Forward and backward (twice the forward) of one trained token of a
    causal sequence of ``seq``: a token attends to (seq + 1) / 2 keys on
    average."""
    return 3.0 * forward_flops_per_token(mc, (seq + 1) / 2.0)


def sandwich_sites(mc: Dict):
    """(n_in, n_out, count per token) of the butterfly sites."""
    return [(i, o, c) for s, i, o, c in projections(mc)
            if _is_butterfly(mc, s)]


def sandwich_row_flops(mc: Dict) -> float:
    """Forward operations of the butterfly sites for one token."""
    k = mc["butterfly"]["k_factor"]
    return float(sum(c * sandwich_flops(i, o, k)
                     for i, o, c in sandwich_sites(mc)))


def sandwich_row_bytes(mc: Dict) -> float:
    """Bytes one token moves through the butterfly sites: its bf16 input
    and output rows (the stage weights are counted per call)."""
    return float(sum(c * (i + o) * BF16 for i, o, c in sandwich_sites(mc)))
