"""Operations and bytes of the two-mixer block from its shapes
(``perf/sala_weights.py:SalaSizes``): the yardstick of the ``*.sala``
metrics and of ``sparse_attn_roofline``, by the rules of
``perf/counts.py`` (what the algorithm needs, a multiply-add is two
operations, the embedding lookup is not counted, padding is not counted).

What the algorithm needs of a sparse layer is the published computation:
every visible row up to ``dense_len`` rows of context; past it the
selector's scores against the compressed keys and the rows of ``topk``
blocks. Of a linear layer: the recurrence, ``k^T v`` into the state and
``q S`` out of it, whatever chunked form computes it.
"""

from __future__ import annotations

import math

from .sala_weights import GAINS, LINEAR, SPARSE, block_shapes


def num_params(s) -> int:
    blocks = sum(math.prod(shape) for i in range(s.num_layers)
                 for shape in block_shapes(s, i).values())
    return 2 * s.vocab * s.d_model + s.d_model + blocks


def fixed_matmul_params(s) -> int:
    """Matrix entries every token is multiplied with: the mixers' five
    projections and the FFN (the head: :func:`serve_flops`)."""
    return sum(math.prod(shape) for i in range(s.num_layers)
               for n, shape in block_shapes(s, i).items() if n not in GAINS)


def layers_of(s, mixer: str) -> int:
    return s.mixers.count(mixer)


def state_bytes(s) -> int:
    """One slot's state over the linear layers, fp32."""
    return layers_of(s, LINEAR) * s.num_heads * s.head_dim ** 2 * 4


def page_bytes(s, itemsize: int = 2) -> int:
    """One K/V head's page of K and of V in one sparse layer."""
    return 2 * s.block_size * s.head_dim * itemsize


def recurrence_flops(s) -> int:
    """One token through one linear layer's recurrence, all heads."""
    return 4 * s.num_heads * s.head_dim ** 2


def pair_flops(s) -> int:
    """QK^T and PV of one (query, row) pair over all query heads."""
    return 4 * s.num_heads * s.head_dim


def attended(s, t: int) -> tuple[int, int]:
    """``(rows, compressed keys)`` a query at position ``t`` reads in a
    sparse layer: every row up to ``dense_len`` rows of context and no
    key; past it the rows of ``topk`` blocks (its own block as far as it
    has come) and every compressed key that is defined."""
    if t + 1 <= s.dense_len:
        return t + 1, 0
    rows = (s.topk - 1) * s.block_size + t % s.block_size + 1
    return rows, (t + 1 - s.kernel_size) // s.kernel_stride + 1


def sparse_flops(s, base: int, n: int, sparse: bool = True) -> float:
    """One sparse layer's attention for the queries at positions ``base
    .. base + n - 1``; ``sparse`` false: a block the program ran dense."""
    if not sparse or base + n <= s.dense_len:
        return pair_flops(s) * (n * base + n * (n + 1) // 2)
    got = [attended(s, t) for t in range(base, base + n)]
    return pair_flops(s) * sum(r for r, _ in got) \
        + 2 * s.num_heads * s.head_dim * sum(k for _, k in got)


def serve_flops(s, prefills, decode_contexts) -> float:
    """Forward FLOPs of prefilling the blocks ``prefills`` (``(base, n,
    sparse)`` each: first position, tokens, whether the program ran the
    selector) and of decoding one token at each of ``decode_contexts``
    (the rows its query sees, its own included). The head is one row a
    prefill block (its last) and one a decoded token."""
    tokens = sum(n for _, n, _ in prefills) + len(decode_contexts)
    heads = len(prefills) + len(decode_contexts)
    sparse = sum(sparse_flops(s, b, n, sp) for b, n, sp in prefills) \
        + sum(sparse_flops(s, c - 1, 1) for c in decode_contexts)
    return (2.0 * tokens * fixed_matmul_params(s)
            + 2.0 * heads * s.d_model * s.vocab
            + tokens * layers_of(s, LINEAR) * recurrence_flops(s)
            + layers_of(s, SPARSE) * sparse)


def selector_bytes(s, contexts, itemsize: int = 2) -> int:
    """The selector's rows one sparse layer reads in a decode tick: for
    each slot past ``dense_len``, a group mean (``kernel_stride`` rows'
    worth) a K/V head for every whole group it holds."""
    groups = sum(c // s.kernel_stride for c in contexts if c > s.dense_len)
    return groups * s.kv_heads * s.head_dim * itemsize


def attended_page_bytes(s, sparse_pages: int, itemsize: int = 2) -> int:
    """K and V of the pages all sparse layers attend in a tick, from the
    tick's ``sparse_pages`` (a (slot, K/V head), one layer)."""
    return layers_of(s, SPARSE) * sparse_pages * page_bytes(s, itemsize)


def decode_tick_bytes(s, sparse_pages: int, state_slots: int, contexts,
                      itemsize: int = 2) -> int:
    """What one decode tick has to move whatever implements it: every
    weight but the embedding table once, each decoding slot's state read
    and written, the attended pages, the selector's rows."""
    fixed = num_params(s) - s.vocab * s.d_model
    return (itemsize * fixed + 2 * state_slots * state_bytes(s)
            + attended_page_bytes(s, sparse_pages, itemsize)
            + layers_of(s, SPARSE) * selector_bytes(s, contexts, itemsize))
