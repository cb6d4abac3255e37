"""Operations and bytes from shapes: the benchmark's own yardstick.

Every count is of what the algorithm needs, not of what a program
happens to execute: causal attention is counted over the T(T+1)/2
pairs a causal mask keeps, rematerialised work is not counted, the
embedding lookup is not counted, and a multiply-add is two operations.
A share of a peak built from these can reach 100 only if the program
does nothing beyond them.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; a kind that is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "perf/peaks.json")
    return table[device_kind]


def num_params(sizes) -> int:
    e, f, v = sizes.d_model, sizes.d_ff, sizes.vocab
    per_block = 4 * e * e + 2 * e * f + f + e + 4 * e
    return v * e + sizes.num_layers * per_block + 2 * e + e * v


def matmul_flops_per_token(sizes) -> int:
    """Forward, one token: the four attention projections, the two MLP
    products and the head."""
    e, f, v = sizes.d_model, sizes.d_ff, sizes.vocab
    return sizes.num_layers * (8 * e * e + 4 * e * f) + 2 * e * v


def attention_flops(sizes, pairs: int) -> int:
    """Forward, all layers: QK^T and PV over ``pairs`` (query, key) pairs."""
    return sizes.num_layers * 4 * sizes.d_model * pairs


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def train_flops_per_token(sizes, seq_len: int) -> float:
    """Forward + backward = 3 x forward, per token of a causal sequence."""
    attn = attention_flops(sizes, causal_pairs(seq_len)) / seq_len
    return 3.0 * (matmul_flops_per_token(sizes) + attn)


def flash_call_flops(batch: int, heads: int, t: int, head_dim: int,
                     products: int) -> int:
    """One causal flash call made of ``products`` T x T x D matrix
    products per head: 2 in the forward kernel (QK^T, PV), 4 in the
    dK/dV backward kernel (QK^T, dO V^T, P^T dO, dS^T Q) and 3 in the dQ
    backward kernel (QK^T, dO V^T, dS K)."""
    return products * 2 * batch * heads * causal_pairs(t) * head_dim


def flash_call_bytes(batch: int, heads: int, t: int, head_dim: int,
                     arrays: int, itemsize: int = 2) -> int:
    """HBM traffic of one call that reads or writes ``arrays`` arrays of
    ``[B, H, T, D]`` once each."""
    return arrays * batch * heads * t * head_dim * itemsize


def kv_bytes_per_token(sizes, itemsize: int = 2) -> int:
    return 2 * sizes.num_layers * sizes.d_model * itemsize


def decode_tick_bytes(sizes, resident_tokens: int, itemsize: int = 2) -> int:
    """What one decode tick has to read: every weight but the embedding
    table once in the compute type, and the resident K/V rows of the
    active slots."""
    weights = num_params(sizes) - sizes.vocab * sizes.d_model
    return (weights * itemsize
            + resident_tokens * kv_bytes_per_token(sizes, itemsize))


def serve_flops(sizes, prompt_lens, decode_contexts) -> int:
    """Forward FLOPs of prefilling prompts of the given lengths and of
    decoding one token at each of the given context lengths (the keys a
    decoded token attends, itself included)."""
    per_tok = matmul_flops_per_token(sizes)
    tokens = sum(prompt_lens) + len(decode_contexts)
    pairs = sum(causal_pairs(p) for p in prompt_lens) + sum(decode_contexts)
    return tokens * per_tok + attention_flops(sizes, pairs)
