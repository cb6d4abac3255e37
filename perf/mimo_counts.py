"""Operations and bytes of the window/global routed-expert family from
its shapes (``perf/mimo_weights.py:MimoSizes``): the yardstick of the
``*.mimo`` metrics, by the rules of ``perf/counts.py`` (what the
algorithm needs, a multiply-add is two operations, the embedding lookup
is not counted, padding is not counted).
"""

from __future__ import annotations

import math

from .mimo_weights import DENSE, MOE, WINDOW, block_shapes


def num_params(s) -> int:
    blocks = sum(math.prod(shape) for i in range(s.num_layers)
                 for shape in block_shapes(s, i).values())
    return 2 * s.vocab * s.d_model + s.d_model + blocks


def expert_params(s) -> int:
    """One expert's three matrices."""
    return 3 * s.d_model * s.expert_ff


def held_expert_params(s) -> int:
    return s.ffn_kinds.count(MOE) * s.held * expert_params(s)


def fixed_matmul_params(s) -> int:
    """Matrix entries every token is multiplied with: the attention
    projections, the dense FFN, the routers and the head."""
    names = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "wr")
    return s.d_model * s.vocab + sum(
        math.prod(shape) for i in range(s.num_layers)
        for n, shape in block_shapes(s, i).items() if n in names)


def expected_assignments(s, tokens: int) -> float:
    """Assignments to held experts that ``tokens`` tokens make over the
    routed layers when routing is uniform: ``top_k * held /
    router_width`` a token a layer."""
    return tokens * s.ffn_kinds.count(MOE) * s.top_k * s.held / s.router_width


def attention_pair_flops(s) -> int:
    """QK^T and PV of one (query, key) pair over all query heads."""
    return 2 * s.num_heads * (s.head_dim + s.v_head_dim)


def prefill_pairs(s, length: int) -> tuple[int, int]:
    """(global, window) pairs a layer of one prompt prefilled whole:
    causal, and ``min(i + 1, window)`` for query ``i``."""
    w = min(length, s.window)
    return (length * (length + 1) // 2,
            w * (w + 1) // 2 + (length - w) * s.window)


def serve_flops(s, prompt_lens, decode_contexts, assigned=None) -> float:
    """Forward FLOPs of prefilling prompts of the given lengths and of
    decoding one token at each of the given contexts (the keys a decoded
    token may attend, itself included). ``assigned``: the counted
    assignments to held experts over the routed layers, where a counter
    is at hand; else the expected share."""
    tokens = sum(prompt_lens) + len(decode_contexts)
    if assigned is None:
        assigned = expected_assignments(s, tokens)
    pairs_g = sum(prefill_pairs(s, p)[0] for p in prompt_lens) \
        + sum(decode_contexts)
    pairs_w = sum(prefill_pairs(s, p)[1] for p in prompt_lens) \
        + sum(min(c, s.window) for c in decode_contexts)
    n_window = s.layer_kinds.count(WINDOW)
    return (2.0 * tokens * fixed_matmul_params(s)
            + 2.0 * assigned * expert_params(s)
            + attention_pair_flops(s) * (
                (s.num_layers - n_window) * pairs_g + n_window * pairs_w))


def kv_row_bytes(s, kind: int, itemsize: int = 2) -> int:
    """One cached token's K and V rows over the layers of one kind."""
    layers = s.layer_kinds.count(kind)
    return layers * s.kv_heads[kind] * (s.head_dim + s.v_head_dim) * itemsize


def decode_tick_bytes(s, contexts, touched: int, itemsize: int = 2) -> int:
    """What one decode tick has to read: every weight but the embedding
    table and the experts once, the experts its tokens touched (summed
    over the routed layers), every resident row of the global layers,
    and the last ``window`` rows of the window layers, of each active
    slot."""
    fixed = num_params(s) - s.vocab * s.d_model - held_expert_params(s)
    return (itemsize * (fixed + touched * expert_params(s))
            + sum(contexts) * kv_row_bytes(s, 0, itemsize)
            + sum(min(c, s.window) for c in contexts)
            * kv_row_bytes(s, WINDOW, itemsize))
