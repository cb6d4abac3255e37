"""Block-sparse attention over a paged pool: the selector, its cache, and
attention over the pages it chose.

A layer of this kind keeps every K and V row, as a global layer does,
but a query past ``dense_len`` rows of context attends ``topk`` blocks of
them, chosen for it by a selector that has no parameters. A block is a
page. :class:`Selector` holds the sizes; the rule, for a query at
position ``t`` (``t + 1`` rows visible):

- ``t + 1 <= dense_len``: every earlier row, causal softmax.
- else: compressed key ``j`` of a K/V head is the mean of that head's K
  rows ``stride j .. stride j + 2 stride - 1``, defined once its last
  row is at or before ``t``. For each query head ``p = softmax_j(q .
  kc_j x scale)`` over the defined ``j``; a K/V head's score is the sum
  of ``p`` over its query heads; block ``b`` scores the maximum over the
  compressed keys that overlap it (``j`` in ``G b - 1 .. G b + G - 1``,
  ``G = block / stride``). Always taken: the first ``init`` blocks and
  the last ``local`` up to the query's own; the rest of the ``topk`` by
  score, ties to the lower index. Causal softmax over the rows of those
  blocks.

**The pools.** K and V of a layer share ONE pool, ``[pages, Hkv, 2 x
page_size, D]``: the head before the row, so that one head's page is one
block of whole tiles whatever ``Hkv`` is (two K/V heads side by side in a
row would be padded to a 16-row tile, 8 times the bytes), and a head's
K rows (``0 .. page_size - 1``) followed by its V rows, so that the
decode kernel fetches both in one copy (16 KB copies of K and of V
apart cost a grid step half as much again as one of 32 KB: PERF.md
section 6, PR 34). The selector's cache, beside it through the same
block table: ``[pages x Hkv x G, D]``, row ``(page
Hkv + head) G + g`` the MEAN of that head's K rows ``g stride .. g stride
+ stride - 1`` of the page. A compressed key is half the sum of two
neighbouring group means, which may lie on two pages; a group never
does. The program that writes a K row writes its group's mean: a prefill
block from its own rows (it starts on a group's first row), a decode
tick from the pool's rows of the group. A group that is not yet whole
holds a mean of stale rows, which no query reads: key ``j`` is defined
only once both its groups are whole, and the tick that writes a group's
last row writes its true mean.

**Three forms of one attention.** :func:`attend_blocks` over a gathered
view and a block mask (a decode tick off the TPU, the uncached forward);
:func:`attend_paged`, a prefill block of one slot: the queries in blocks,
each against the slot's pages in key blocks up to its own position, a
query's mask its own (right, not fast: the masked form still multiplies
the blocks it drops); :func:`sparse_decode_attention`, a Pallas kernel
that reads, for each (slot, K/V head), the listed pages in place.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_cache import _MASKED
from .paged_attention import _fetch_plan


class Selector(NamedTuple):
    """The selector's sizes (``models.hybrid.HybridSpec.selector``)."""

    block: int       # rows a block; the pool's page
    stride: int      # rows a group; a compressed key spans two groups
    topk: int        # blocks a query past ``dense_len`` attends
    init: int        # leading blocks always taken
    local: int       # trailing blocks always taken, the query's own last
    dense_len: int   # rows of context up to which every row is attended

    @property
    def groups(self) -> int:
        return self.block // self.stride

    def list_width(self, pages: int) -> int:
        """Entries of a decode tick's page list over a table of ``pages``
        columns: all of a slot's pages up to ``dense_len``, else
        ``topk``."""
        return min(pages, max(self.topk, -(-self.dense_len // self.block)))


# -- the pools ----------------------------------------------------------------


def head_major_rows(table, positions, page_size: int, num_pages: int,
                    heads: int, rows: int | None = None, first: int = 0):
    """Flat rows ``[B, T, heads]`` of ``positions [B, T]`` in a ``[pages,
    heads, rows, D]`` pool viewed ``[pages x heads x rows, D]``, through
    ``table [B, TP]``: position ``p`` lies in the table's page ``p //
    page_size`` at row ``first + p % page_size`` of each head's ``rows``
    (``page_size`` of them where not given). A negative position or an
    unmapped page maps past the pool's end, so a scatter drops it."""
    rows = page_size if rows is None else rows
    tp = table.shape[1]
    page = jnp.maximum(positions, 0) // page_size
    pid = jnp.take_along_axis(table, jnp.clip(page, 0, tp - 1), axis=1)
    ok = (positions >= 0) & (page < tp) & (pid >= 0)
    h = jnp.arange(heads, dtype=jnp.int32)
    at = ((pid[..., None] * heads + h) * rows
          + (first + positions % page_size)[..., None])
    return jnp.where(ok[..., None], at, num_pages * heads * rows)


def write_rows(pool, new, rows):
    """``new [B, T, H, D]`` into ``pool [pages, H, rows, D]`` at
    :func:`head_major_rows`' ``rows [B, T, H]``, in place under
    donation."""
    d = pool.shape[-1]
    flat = pool.reshape(-1, d).at[rows.reshape(-1)].set(
        new.reshape(-1, d).astype(pool.dtype))
    return flat.reshape(pool.shape)


def write_means(sel_pool, means, first, table, sel: Selector, heads: int):
    """Group means ``means [B, N, H, D]`` of the groups whose first rows
    are at positions ``first [B, N]`` (negative: dropped) into the
    selector's cache ``sel_pool [pages x H x G, D]``."""
    g = sel.groups
    rows = head_major_rows(table, jnp.where(first >= 0, first // sel.stride,
                                            -1), g,
                           sel_pool.shape[0] // (heads * g), heads)
    return sel_pool.at[rows.reshape(-1)].set(
        means.reshape(-1, means.shape[-1]).astype(sel_pool.dtype))


def group_means(k, stride: int):
    """Means of ``k [B, T, H, D]`` over groups of ``stride`` rows, ``[B,
    ceil(T / stride), H, D]`` fp32 (a last group short of rows: zeros
    stand in, and no query reads it)."""
    b, t, h, d = k.shape
    n = -(-t // stride)
    k = jnp.pad(k.astype(jnp.float32),
                ((0, 0), (0, n * stride - t), (0, 0), (0, 0)))
    return k.reshape(b, n, stride, h, d).mean(axis=2)


def pool_group_means(pool, table, positions, sel: Selector):
    """The mean of the group of K rows that holds ``positions [B]`` (one
    a slot), from the pool's own rows: ``[B, 1, H, D]`` fp32. The rows
    are read through the pool's flat view, as they are written: indexing
    pages and rows apart makes the compiler turn the whole pool."""
    pages, heads, rows, d = pool.shape
    first = jnp.maximum(positions, 0) // sel.stride * sel.stride
    at = head_major_rows(table, first[:, None] + jnp.arange(sel.stride),
                         sel.block, pages, heads, rows)  # [B, stride, H]
    got = pool.reshape(-1, d)[jnp.minimum(at, pages * heads * rows - 1)]
    return got.astype(jnp.float32).mean(axis=1)[:, None]


def table_means(sel_pool, table, sel: Selector, heads: int):
    """A slot's group means through its table: ``[B, TP x G, H, D]``."""
    g, (b, tp) = sel.groups, table.shape
    rows = (jnp.maximum(table, 0) * (heads * g))[..., None] \
        + jnp.arange(heads * g)
    m = sel_pool[rows].reshape(b, tp, heads, g, -1)
    return m.transpose(0, 1, 3, 2, 4).reshape(b, tp * g, heads, -1)


def gather_heads(pool, table):
    """A slot's K and V rows through its table, a head at a time: two
    views ``[B, H, TP x page_size, D]``."""
    got = pool[jnp.maximum(table, 0)]               # [B, TP, H, 2 S, D]
    b, tp, h, rows, d = got.shape
    view = lambda a: a.transpose(0, 2, 1, 3, 4).reshape(b, h, -1, d)
    return view(got[..., :rows // 2, :]), view(got[..., rows // 2:, :])


# -- the selector -------------------------------------------------------------

_FORCED = 1e9


def select_blocks(q, means, q_pos, sel: Selector, scale: float):
    """The blocks each query attends past ``dense_len``: ``q [B, T, Hq,
    D]`` at ``q_pos [B, T]`` (negative: padding) against group means
    ``means [B, blocks x G, Hkv, D]`` (group ``g`` the mean of rows ``g
    stride ..``) -> a mask ``[B, T, Hkv, blocks]`` of ``min(topk,
    blocks)`` blocks (fewer where a query has fewer behind it).

    The top ``k`` are taken by RANK, not by a sort: a block is chosen
    when fewer than ``k`` blocks beat it (a higher score, or the same
    score at a lower index), one fused compare-and-count over ``[blocks,
    blocks]``. On the chip a sort of 560 scores a (slot, K/V head) cost
    0.62 ms a layer a decode tick and a quarter of a prefill chunk past
    ``dense_len`` (PERF.md section 6, PR 34)."""
    b, t, hq, d = q.shape
    hkv, g = means.shape[2], sel.groups
    nb = means.shape[1] // g
    f32 = jnp.float32
    qg = q.reshape(b, t, hkv, hq // hkv, d)
    u = jnp.einsum("bthgd,bjhd->bhgtj", qg, means.astype(q.dtype),
                   preferred_element_type=f32)
    s = 0.5 * scale * (u[..., :-1] + u[..., 1:])      # key j: groups j, j + 1
    last_row = (jnp.arange(nb * g - 1) + 2) * sel.stride - 1
    defined = (last_row[None, None, :] <= q_pos[:, :, None])[:, None]
    p = jax.nn.softmax(jnp.where(defined[:, :, None], s, _MASKED), axis=-1)
    p = jnp.where(defined[:, :, None], p, 0.0).sum(axis=2)  # [B, Hkv, T, J]
    p = jnp.where(defined, p, -1.0)
    # Block b pools keys G b - 1 .. G b + G - 1: with key -1 in front,
    # its own row of ``[blocks + 1, G]`` and the next row's first.
    none = jnp.full((*p.shape[:-1], 1), -1.0, f32)
    r = jnp.concatenate([none, p] + [none] * g, axis=-1).reshape(
        *p.shape[:-1], nb + 1, g)
    pooled = jnp.maximum(r[..., :-1, :].max(axis=-1), r[..., 1:, 0])
    blocks = jnp.arange(nb)
    own = (q_pos // sel.block)[..., None]             # [B, T, 1]
    forced = (blocks < sel.init) | (blocks > own - sel.local)
    there = (blocks <= own)[:, None]                  # [B, 1, T, blocks]
    score = jnp.where(forced[:, None], _FORCED, pooled)
    score = jnp.where(there, score, -_FORCED)
    mine, other = score[..., :, None], score[..., None, :]
    beats = (other > mine) | ((other == mine)
                              & (blocks[None, :] < blocks[:, None]))
    chosen = (beats.sum(axis=-1) < min(sel.topk, nb)) & there
    return chosen.transpose(0, 2, 1, 3)


def attends_all(q_pos, sel: Selector):
    """Queries whose context is within ``dense_len``."""
    return q_pos < sel.dense_len


def allowed_blocks(chosen, dense):
    """``select_blocks``' mask, with every block allowed to a query that
    attends every row (``dense [...]``: the causal mask does the rest)."""
    return chosen | dense[..., None, None]


def listed_blocks(chosen, q_pos, sel: Selector, blocks: int, heads: int):
    """The blocks a decode tick's queries read, ``[B, heads, blocks]``:
    the chosen ones (``chosen [B, heads, blocks]``; ``None`` where no
    slot can be past ``dense_len``), or for a query within ``dense_len``
    (``q_pos [B]``; negative: none) all of its own."""
    own = (q_pos // sel.block)[:, None, None]
    every = jnp.broadcast_to(
        (jnp.arange(blocks) <= own) & (q_pos >= 0)[:, None, None],
        (q_pos.shape[0], heads, blocks))
    if chosen is None:
        return every
    return jnp.where(attends_all(q_pos, sel)[:, None, None], every, chosen)


def pack_blocks(listed, width: int):
    """A block mask ``[..., blocks]`` as a list ``[..., width]`` in rising
    order, ``-1`` beyond. Packed by counting, not sorting: entry ``w`` is
    the listed block with ``w`` listed blocks before it."""
    at = jnp.cumsum(listed, axis=-1) - 1
    hit = listed[..., None] & (at[..., None] == jnp.arange(width))
    return jnp.max(jnp.where(hit, jnp.arange(listed.shape[-1])[:, None], -1),
                   axis=-2)


# -- attention over a gathered view and a block mask --------------------------


def attend_blocks(q, k_view, v_view, q_pos, allowed, block: int,
                  scale: float):
    """``q [B, T, Hq, D]`` at ``q_pos [B, T]`` against ``k_view``/``v_view
    [B, Hkv, C, D]`` whose row ``c`` holds position ``c``; ``allowed [B,
    T, Hkv, blocks]`` the blocks each query may read (``None``: all).
    Causal, fp32 softmax -> ``[B, T, Hq, Dv]`` in ``v_view``'s dtype."""
    b, t, hq, d = q.shape
    hkv, c = k_view.shape[1], k_view.shape[2]
    qg = q.reshape(b, t, hkv, hq // hkv, d)
    s = jnp.einsum("bthgd,bhcd->bhgtc", qg, k_view,
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(c)
    ok = (k_pos <= q_pos[..., None])[:, None]                # [B, 1, T, C]
    if allowed is not None:
        rows = jnp.repeat(allowed, block, axis=-1)[..., :c]  # [B, T, Hkv, C]
        ok = ok & rows.transpose(0, 2, 1, 3)
    ok = ok[:, :, None]                                      # [B, h, 1, T, C]
    s = jnp.where(ok, s, _MASKED)
    e = jnp.where(ok, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    den = e.sum(axis=-1, keepdims=True)
    p = (e / jnp.where(den > 0, den, 1.0)).astype(v_view.dtype)
    out = jnp.einsum("bhgtc,bhcd->bthgd", p, v_view)
    return out.reshape(b, t, hq, v_view.shape[-1])


QUERY_BLOCK = 512
KEY_PAGES = 16


def attend_paged(q, pool, table, q_pos, allowed, block: int, scale: float):
    """One slot's prefill block: ``q [T, Hq, D]`` at ``q_pos [T]``
    (negative: padding) against the pages ``table [TP]`` maps, which hold
    rows ``0 ..`` of the slot (this block's own among them); ``allowed
    [T, Hkv, TP]``. The queries go in blocks, each against key blocks of
    ``KEY_PAGES`` pages up to its own last position: the work follows the
    context that is there, not the table's width. Online softmax in
    fp32 -> ``[T, Hq, D]`` in the pool's dtype."""
    t, hq, d = q.shape
    hkv, tp = pool.shape[1], table.shape[0]
    qb, kp = math.gcd(t, QUERY_BLOCK), math.gcd(tp, KEY_PAGES)
    kr, grp = kp * block, hq // hkv
    f32 = jnp.float32

    def one(args):
        qs, ps, al = args            # [qb, Hq, D], [qb], [qb, Hkv, TP]
        qg = qs.reshape(qb, hkv, grp, d)

        def body(i, carry):
            m, l, acc = carry
            ids = jnp.maximum(lax.dynamic_slice_in_dim(table, i * kp, kp), 0)
            got = pool[ids].astype(q.dtype)                  # [kp, H, 2 S, D]
            heads = lambda a: a.transpose(1, 0, 2, 3).reshape(hkv, kr, d)
            s = jnp.einsum("thgd,hcd->hgtc", qg, heads(got[:, :, :block]),
                           preferred_element_type=f32) * scale
            k_pos = i * kr + jnp.arange(kr)
            rows = jnp.repeat(lax.dynamic_slice_in_dim(al, i * kp, kp, axis=2),
                              block, axis=-1)                # [qb, Hkv, kr]
            ok = (rows & (k_pos <= ps[:, None])[:, None]).transpose(
                1, 0, 2)[:, None]                            # [Hkv, 1, qb, kr]
            s = jnp.where(ok, s, _MASKED)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            acc = alpha * acc + jnp.einsum(
                "hgtc,hcd->hgtd", p.astype(q.dtype),
                heads(got[:, :, block:]), preferred_element_type=f32)
            return m_new, alpha * l + p.sum(axis=-1, keepdims=True), acc

        shape = (hkv, grp, qb)
        init = (jnp.full((*shape, 1), _MASKED, f32),
                jnp.zeros((*shape, 1), f32),
                jnp.zeros((*shape, d), f32))
        _, l, acc = lax.fori_loop(0, (ps.max() + kr) // kr, body, init)
        out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
        return out.transpose(2, 0, 1, 3).reshape(qb, hq, -1)

    blocks = lambda a: a.reshape(t // qb, qb, *a.shape[1:])
    out = lax.map(one, (blocks(q), blocks(q_pos), blocks(allowed)))
    return out.reshape(t, hq, -1).astype(pool.dtype)


# -- the decode kernel: the listed pages, in place ----------------------------

# Pages a grid step reads of one (slot, K/V head), 32 KB each at the
# published widths (64 rows of K, then 64 of V, of 128 bf16), their K rows
# stacked in VMEM into one ``[pages x page_size, D]`` K so that a step's
# scores are whole lane tiles.
PAGES_PER_STEP = 16


def kernel_accepts(group_heads: int, head_dim: int, page_size: int) -> bool:
    """Whether :func:`sparse_decode_attention`'s tiles fit: heads of whole
    128-lane tiles, a K/V head's query heads whole 8-row tiles, a page
    whole 16-row tiles (bf16's) so that pages stack for free."""
    return (head_dim % 128 == 0 and group_heads % 8 == 0
            and page_size % 16 == 0)


def _kernel(fetch_ref, block_ref, qpos_ref, q_ref, *refs, group: int,
            page_size: int, scale: float):
    del fetch_ref  # the index maps' operand
    kv_refs = refs[:group]                      # [1, 1, 2 S, D]: K, then V
    o_ref, m_ref, l_ref, acc_ref = refs[group:]
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at = ((b * pl.num_programs(1) + h) * pl.num_programs(2) + j) * group
    held = [block_ref[at + g] for g in range(group)]   # logical block or -1

    @pl.when(functools.reduce(jnp.logical_or, [x >= 0 for x in held]))
    def _():
        q = q_ref[0, 0]                                     # [Gq, D]
        k = jnp.concatenate([r[0, 0, :page_size] for r in kv_refs],
                            axis=0).astype(q.dtype)
        v = jnp.concatenate([r[0, 0, page_size:] for r in kv_refs],
                            axis=0).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [Gq, G * S]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, group * page_size), 1)
        pos = jnp.full_like(lane, -1)
        for g in range(group):  # an entry of -1 holds the last page fetched
            pos = jnp.where(lane // page_size == g,
                            held[g] * page_size + lane - g * page_size, pos)
        ok = (pos >= 0) & (pos <= qpos_ref[b])
        s = jnp.where(ok, s, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(q.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        o_ref[0, 0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_step",
                                             "interpret"))
def sparse_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    table: jax.Array,
    blocks: jax.Array,
    q_pos: jax.Array,
    *,
    scale: float,
    pages_per_step: int = PAGES_PER_STEP,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of one query a slot over LISTED pages, read where
    they lie: ``q [B, Hkv, Gq, D]`` (a K/V head's query heads together) at
    positions ``q_pos [B]``; ``pool [P, Hkv, 2 S, D]`` (a head's K rows,
    then its V rows); ``table [B, TP]`` the slot's page ids; ``blocks [B, Hkv, W]`` the logical
    blocks (table columns) each (slot, K/V head) attends, ``-1`` = none:
    block ``c``'s rows hold positions ``c S ..``. Returns ``[B, Hkv, Gq,
    D]`` in ``q``'s dtype: what :func:`attend_blocks` gives over the
    gathered view with those blocks allowed, to rounding (online
    softmax). A (slot, head) with nothing listed gets zeros.

    Jitted, so the layers of one program are one traced function lowered
    once. A grid step reads ``pages_per_step`` listed pages; an entry of
    ``-1`` names the page fetched last (no copy is issued), and a step
    with none listed skips its compute. The bytes moved follow the
    list, not the table."""
    b, hkv, gq, d = q.shape
    page_size = pool.shape[2] // 2
    w = blocks.shape[-1]
    group = math.gcd(pages_per_step, w)
    steps = w // group
    pid = jnp.take_along_axis(table[:, None, :].repeat(hkv, axis=1),
                              jnp.maximum(blocks, 0), axis=-1)
    pid = jnp.where(blocks >= 0, pid, -1)
    listed = jnp.where(pid >= 0, blocks, -1).reshape(-1).astype(jnp.int32)
    fetch, _ = _fetch_plan(pid.reshape(b * hkv, w), group)

    def page(g):
        def index(bi, hi, ji, fetch_ref, block_ref, qpos_ref):
            return (fetch_ref[((bi * hkv + hi) * steps + ji) * group + g],
                    hi, 0, 0)
        return index

    head = lambda bi, hi, ji, *_: (bi, hi, 0, 0)
    pages = [pl.BlockSpec((1, 1, 2 * page_size, d), page(g))
             for g in range(group)]
    return pl.pallas_call(
        functools.partial(_kernel, group=group, page_size=page_size,
                          scale=scale),
        name="sparse_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, steps),
            in_specs=[pl.BlockSpec((1, 1, gq, d), head)] + pages,
            out_specs=pl.BlockSpec((1, 1, gq, d), head),
            scratch_shapes=[pltpu.VMEM((gq, 1), jnp.float32),
                            pltpu.VMEM((gq, 1), jnp.float32),
                            pltpu.VMEM((gq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(fetch, listed, q_pos.astype(jnp.int32), q, *[pool] * group)
