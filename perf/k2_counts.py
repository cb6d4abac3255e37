"""Operations and bytes of the latent-attention routed-expert block from
its shapes (``perf/k2_weights.py:K2Sizes``): the yardstick of the
``*.k2`` metrics, by the rules of ``perf/counts.py`` (what the algorithm
needs, a multiply-add is two operations, the embedding lookup is not
counted, padding is not counted).
"""

from __future__ import annotations

import math

from .k2_weights import MOE, block_shapes

# The matrices every token is multiplied with. ``wkvb`` stands for the
# up-projection of a prefilled token's row and, in a decode tick, for the
# absorbed products (``W_uk`` into the query, ``W_uv`` onto the output):
# the same 512 x 64 x 256 entries either way.
EVERY_TOKEN = ("wqa", "wqb", "wkva", "wkvb", "wo", "wg", "wu", "wd", "wr",
               "sg", "su", "sd")


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
    """Matrix entries every token is multiplied with: the attention's
    five projections, the dense FFN, the routers, the shared experts and
    the head."""
    return s.d_model * s.vocab + sum(
        math.prod(shape) for i in range(s.num_layers)
        for n, shape in block_shapes(s, i).items() if n in EVERY_TOKEN)


def expected_assignments(s, tokens: int) -> float:
    """Assignments to held experts that ``tokens`` tokens make over the
    routed layers when routing is uniform."""
    return tokens * s.ffn_kinds.count(MOE) * s.top_k * s.held / s.router_width


def prefill_pair_flops(s) -> int:
    """QK^T and PV of one (query, key) pair over all heads, published
    form: heads of ``nope + rope`` and ``v``."""
    return 2 * s.num_heads * (s.nope_dim + s.rope_dim + s.v_head_dim)


def decode_pair_flops(s) -> int:
    """The same in the absorbed form: every head reads the row, ``kv_lora
    + rope`` wide for the score and ``kv_lora`` for the output."""
    return 2 * s.num_heads * (s.latent_row + s.kv_lora)


def serve_flops(s, prompt_lens, decode_contexts, assigned=None) -> float:
    """Forward FLOPs of prefilling prompts of the given lengths (causal
    pairs, published form) and of decoding one token at each of the given
    contexts (the rows a decoded token attends, its own included;
    absorbed form). ``assigned``: the counted assignments to held experts
    over the routed layers, where a counter is at hand; else the expected
    share."""
    tokens = sum(prompt_lens) + len(decode_contexts)
    if assigned is None:
        assigned = expected_assignments(s, tokens)
    pairs = sum(p * (p + 1) // 2 for p in prompt_lens)
    return (2.0 * tokens * fixed_matmul_params(s)
            + 2.0 * assigned * expert_params(s)
            + s.num_layers * (prefill_pair_flops(s) * pairs
                              + decode_pair_flops(s) * sum(decode_contexts)))


def latent_row_bytes(s, itemsize: int = 2) -> int:
    """One cached token's rows over all layers."""
    return s.num_layers * s.latent_row * itemsize


def decode_tick_bytes(s, latent_rows: int, touched: int,
                      itemsize: int = 2) -> int:
    """What one decode tick has to read whatever implements it: every
    weight but the embedding table and the experts once, the experts its
    tokens touched (summed over the routed layers), and every cached row
    its active slots attend."""
    fixed = num_params(s) - s.vocab * s.d_model - held_expert_params(s)
    return (itemsize * (fixed + touched * expert_params(s))
            + latent_rows * latent_row_bytes(s, itemsize))
