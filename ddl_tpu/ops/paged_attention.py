"""Decode attention that reads a paged pool's pages in place: the dense
family's stacked K and V pools (:func:`paged_decode_attention`), the
second family's latent pools (:func:`latent_decode_attention`) and its
global layers' grouped-query pools (:func:`grouped_decode_attention`, at
the end).

A dense paged DECODE program (one query a slot) used to gather every
slot's pages into ``[B, TP * page_size, H, D]`` views of K and of V, a
layer at a time, at the WIDEST slot's page bucket, and
``ops.kv_cache.attend`` then read the copies back: 84% of
``serve-1b-closed32``'s decode program (PERF.md section 6, PR 31). This
kernel walks each slot's block table instead and reads the pages where
they lie in the stacked ``[L, pages, page_size, H, D]`` pool: the bytes
moved follow the pages RESIDENT, no view is written, and the pool keeps
its layout (every other program reads and writes it as before).

How it reads a page. The stack is viewed as ``[L * pages, page_size * H,
D]``: leading axes merged, and rows and heads merged into one axis, which
on the chip is the same bytes when ``H`` is a whole number of 8-row tiles
(the tiled layout of ``[..., H, D]`` puts the heads on the sublanes; the
view ``[..., page_size, H * D]`` is NOT the same bytes and costs a copy
of the whole pool). One page is then a ``[page_size * H, D]`` matrix
whose row ``s * H + h`` is key ``s`` of head ``h``, and the MXU takes all
heads at once: ``q [H, D] x page^T -> [H, page_size * H]``, of which
entry ``(h, s * H + h')`` counts where ``h == h'``. The other seven
eighths are masked like any key the query may not see: the MXU's time is
the page passing through it either way, and no head is ever sliced out
of the sublanes.

Which keys count is ``ops.kv_cache.attend``'s rule, from the page's own
positions: ``k_pos <= q_pos`` (``PAD_POS`` rows never), so shared prefix
pages, stale rows of a reused page and a table wider than the slot
behave as in the gathered path. A slot with no mapped page gives zeros.
Online softmax over pages, fp32 running max, sum and accumulator.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_cache import _MASKED, gather_pages

# Pages a grid step reads, each through its own BlockSpec of the same
# pool. Timed on one v5e at ``serve-1b-closed32``'s shape (32 slots, 286
# of 1,024 table entries mapped, 150 MB to read; PERF.md section 6, PR
# 31): a layer's call took 0.293 ms at 2 pages a step and at 4, 0.304 at
# 8, and 0.306 at 2 with a softmax update of its own for each page; the
# same grid copying pages and computing nothing 0.248, computing on one
# page and copying none 0.173; the gathered views and ``attend`` 1.916.
PAGES_PER_STEP = 2


def kernel_accepts(num_heads: int, head_dim: int, page_size: int) -> bool:
    """Whether :func:`paged_decode_attention`'s tiles fit these widths:
    ``head_dim`` whole 128-lane tiles, heads whole 8-row tiles (so rows
    and heads merge for free), and a page of at least one lane tile of
    scores."""
    return (head_dim % 128 == 0 and num_heads % 8 == 0
            and (page_size * num_heads) % 128 == 0)


def _fetch_plan(table: jax.Array, group: int):
    """What each of the ``group`` page inputs of a kernel here fetches at
    each grid step of ``table [B, TP]``, flat ``[B * TP]``: the table's
    page where it is mapped, else the page that input fetched last (an
    unchanged block index issues no copy); and whether it is mapped,
    ``[B * steps, group]``."""
    b, tp = table.shape
    steps = tp // group
    at = jnp.arange(b * tp, dtype=jnp.int32).reshape(b * steps, group)
    mapped = (table >= 0).reshape(b * steps, group)
    last = jax.lax.cummax(jnp.where(mapped, at, -1), axis=0)
    fetch = jnp.where(
        last >= 0, table.reshape(-1)[jnp.maximum(last, 0)], 0).reshape(-1)
    return fetch, mapped


def _kernel(fetch_ref, mapped_ref, qpos_ref, layer_ref, q_ref, *refs,
            group: int, num_heads: int, scale: float):
    del fetch_ref, layer_ref  # the index maps' operands
    pos_refs, k_refs, v_refs = (refs[i * group:(i + 1) * group]
                                for i in range(3))
    o_ref, m_ref, l_ref, acc_ref = refs[3 * group:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                            # [H, D]
    cols = k_refs[0].shape[1]                               # page_size * H
    own_head = (
        jax.lax.broadcasted_iota(jnp.int32, (num_heads, cols), 1) % num_heads
        == jax.lax.broadcasted_iota(jnp.int32, (num_heads, cols), 0))
    at = (b * pl.num_programs(1) + j) * group
    mapped = [mapped_ref[at + g] > 0 for g in range(group)]

    # One softmax update for the step's pages: their products are
    # independent, so the MXU takes them back to back. An unmapped page
    # of a step that has a mapped one holds the page its input fetched
    # last, and is masked whole.
    @pl.when(functools.reduce(jnp.logical_or, mapped))
    def _():
        oks, scores = [], []
        for g in range(group):
            # [S * H, D], in the query's dtype as the gathered view is
            k = k_refs[g][0].astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, S * H]
            ok = own_head & (pos_refs[g][0] <= qpos_ref[b]) & mapped[g]
            oks.append(ok)
            scores.append(jnp.where(ok, s, _MASKED))
        m_prev = m_new = m_ref[...]
        for s in scores:
            m_new = jnp.maximum(m_new, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l, acc = alpha * l_ref[...], alpha * acc_ref[...]
        for g in range(group):
            p = jnp.where(oks[g], jnp.exp(scores[g] - m_new), 0.0)
            l = l + p.sum(axis=-1, keepdims=True)
            acc = acc + jnp.dot(
                p.astype(q.dtype), v_refs[g][0].astype(q.dtype),
                preferred_element_type=jnp.float32)
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "pages_per_step", "interpret"))
def paged_decode_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    pool_pos: jax.Array,
    table: jax.Array,
    q_pos: jax.Array,
    layer: int | jax.Array,
    *,
    scale: float | None = None,
    pages_per_step: int = PAGES_PER_STEP,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of one query a slot against layer ``layer`` of
    the stacked pool, through the block table: ``q [B, H, D]`` at
    positions ``q_pos [B]``; ``pool_k``/``pool_v [L, P, S, H, D]``;
    ``pool_pos [P, S]`` the position each row of a page holds; ``table
    [B, TP]`` each slot's page ids (``-1`` = unmapped). Returns ``[B, H,
    D]`` in ``q``'s dtype: what ``attend(q[:, None], gather_pages(pool_k,
    table, layer).astype(q.dtype), gather_pages(pool_v, table,
    layer).astype(q.dtype), q_pos[:, None], table_positions(pool_pos,
    table))[:, 0]`` gives, to rounding (fp32 scores straight from the
    product, online softmax).

    ``layer`` reaches the kernel as a scalar operand and the function is
    jitted, so the calls of one program are one traced function lowered
    once: lowering a Pallas kernel costs the host a tenth of a second,
    and sixteen of them in each of seven decode buckets showed as 20 s
    of ``setup_s``. A grid step reads ``pages_per_step`` pages of a slot;
    a step or a page past the slot's last mapped one names the page
    fetched last (no new copy is issued) and skips its compute."""
    b, h, d = q.shape
    num_layers, num_pages, page_size = pool_k.shape[:3]
    tp = table.shape[1]
    group = math.gcd(pages_per_step, tp)
    steps = tp // group
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    cols = page_size * h

    fetch, mapped = _fetch_plan(table, group)
    layer_off = (jnp.asarray(layer, jnp.int32) * num_pages).reshape(1)

    stack = lambda pool: pool.reshape(num_layers * num_pages, cols, d)
    pos_rows = jnp.repeat(pool_pos, h, axis=1).reshape(num_pages, 1, cols)

    def page(g, layered):
        def index(bi, ji, fetch_ref, mapped_ref, qpos_ref, layer_ref):
            pid = fetch_ref[(bi * steps + ji) * group + g]
            return ((layer_ref[0] + pid) if layered else pid, 0, 0)
        return index

    slot = lambda bi, ji, *_: (bi, 0, 0)
    in_specs = [pl.BlockSpec((1, h, d), slot)]
    in_specs += [pl.BlockSpec((1, 1, cols), page(g, False))
                 for g in range(group)]
    in_specs += [pl.BlockSpec((1, cols, d), page(g, True))
                 for g in range(group)] * 2
    return pl.pallas_call(
        functools.partial(_kernel, group=group, num_heads=h, scale=scale),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, d), slot),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(fetch, mapped.reshape(-1).astype(jnp.int32), q_pos.astype(jnp.int32),
      layer_off, q, *[pos_rows] * group, *[stack(pool_k)] * group,
      *[stack(pool_v)] * group)


# -- latent rows: one K/V head, V inside K ------------------------------------
#
# A latent layer's pool (``serve.cache.hybrid_cache``) is ``[pages,
# page_size, W]``: a row ``[c | k_r | zeros]`` a token, ``W`` whole lane
# tiles. In the absorbed form (``models.hybrid.latent_absorbed``) every
# query head reads that row as it lies: a page IS the ``[page_size, W]``
# K matrix of all heads, and its first ``v_width`` lanes are V. So the MXU
# form needs no head mask, one copy of a page serves both products, and
# no position is stored: row ``r`` of table column ``c`` holds position
# ``c * page_size + r``.

# Pages a grid step reads (80 KB each at the published widths: 64 rows of
# 640 bf16), stacked in VMEM into one ``[pages * page_size, W]`` K so that
# the scores of a step are whole lane tiles and the MXU sees one product.
# Timed on one v5e at ``serve-k2-closed64-long``'s shape (64 slots, 3,399
# of 17,408 table entries mapped, 215,839 rows; PERF.md section 6, PR
# 32): a layer's call took 1.925 / 1.441 / 1.223 / 1.162 / 1.141 ms at 2
# / 4 / 8 / 16 / 34 pages a step; the gathered view and ``attend_grouped``
# 6.138.
LATENT_PAGES_PER_STEP = 16


def latent_kernel_accepts(num_heads: int, row_width: int, v_width: int,
                          page_size: int) -> bool:
    """Whether :func:`latent_decode_attention`'s tiles fit: rows and
    their V part whole 128-lane tiles, heads whole 8-row tiles, a page
    whole 16-row tiles (bf16's) so that pages stack for free."""
    return (row_width % 128 == 0 and v_width % 128 == 0
            and 0 < v_width <= row_width and num_heads % 8 == 0
            and page_size % 16 == 0)


def _latent_kernel(fetch_ref, mapped_ref, qpos_ref, q_ref, *refs, group: int,
                   page_size: int, v_width: int, scale: float):
    del fetch_ref  # the index maps' operand
    k_refs = refs[:group]
    o_ref, m_ref, l_ref, acc_ref = refs[group:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at = (b * pl.num_programs(1) + j) * group
    mapped = [mapped_ref[at + g] > 0 for g in range(group)]

    @pl.when(functools.reduce(jnp.logical_or, mapped))
    def _():
        q = q_ref[0]                                        # [H, W]
        k = jnp.concatenate([r[0] for r in k_refs], axis=0).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, G * S]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, group * page_size), 1)
        ok = j * (group * page_size) + lane <= qpos_ref[b]
        for g in range(group):  # an unmapped page holds the last fetched
            ok &= mapped[g] | (lane // page_size != g)
        s = jnp.where(ok, s, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(q.dtype), k[:, :v_width],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "v_width",
                                             "pages_per_step", "interpret"))
def latent_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    table: jax.Array,
    q_pos: jax.Array,
    *,
    scale: float,
    v_width: int,
    pages_per_step: int = LATENT_PAGES_PER_STEP,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of one query a slot, ``q [B, H, W]`` at positions
    ``q_pos [B]`` (negative: the slot attends nothing and gets zeros),
    over the rows of ``pool [P, S, W]`` that ``table [B, TP]`` maps (page
    ids, ``-1`` = unmapped; column ``c``'s rows hold positions ``c * S
    ..``), all heads against ONE K/V head: K a row, V its first
    ``v_width`` values. Returns ``[B, H, v_width]`` in ``q``'s dtype: what
    ``attend_grouped(q[:, None], view[:, :, None], view[:, :, None,
    :v_width], q_pos[:, None], positions, scale=scale)[:, 0]`` gives over
    ``view = gather_pages(pool, table)``, to rounding (online softmax).

    Jitted, so the layers of one program, each with a pool of its own,
    are one traced function lowered once. A grid step reads
    ``pages_per_step`` pages of a slot; a step or a page past the slot's
    last mapped one names the page fetched last (no copy is issued) and a
    step with no mapped page skips its compute."""
    b, h, w = q.shape
    page_size = pool.shape[1]
    tp = table.shape[1]
    group = math.gcd(pages_per_step, tp)
    steps = tp // group
    fetch, mapped = _fetch_plan(table, group)

    def page(g):
        def index(bi, ji, fetch_ref, mapped_ref, qpos_ref):
            return (fetch_ref[(bi * steps + ji) * group + g], 0, 0)
        return index

    slot = lambda bi, ji, *_: (bi, 0, 0)
    return pl.pallas_call(
        functools.partial(_latent_kernel, group=group, page_size=page_size,
                          v_width=v_width, scale=scale),
        name="latent_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((1, h, w), slot)] + [
                pl.BlockSpec((1, page_size, w), page(g))
                for g in range(group)],
            out_specs=pl.BlockSpec((1, h, v_width), slot),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, v_width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(fetch, mapped.reshape(-1).astype(jnp.int32), q_pos.astype(jnp.int32),
      q, *[pool] * group)


# -- grouped rows: a page a block, the head before the row, V beside K ---------
#
# A global layer of ``models.hybrid`` has few K/V heads under many query
# heads, and K rows wider than V rows (4 under 64, 192 and 128 at the
# published widths). Its pool (``serve.cache.hybrid_cache``) is ``[pages,
# Hkv, page_size, W]``: the head before the row, so that one head's rows
# of a page are whole tiles (4 heads side by side in a row would put a
# head at 1.5 lane tiles), and a row ``[k | zeros | v | zeros]``, each
# part filled to whole 128-lane tiles (:func:`grouped_row_widths`: ``[k 192
# | 64 zeros | v 128]``, 384), so that K and V are lane slices of ONE
# block: all of a page, every head's K and V, is contiguous and costs a
# grid step one copy (a step's time follows its count of copies: PERF.md
# section 6, PR 34 and PR 35). The query is padded with
# zeros to K's width. No position is stored: row ``r`` of table column
# ``c`` holds position ``c * page_size + r``.


def grouped_row_widths(head_dim: int, v_head_dim: int) -> tuple[int, int]:
    """``(k_width, row_width)`` of a grouped pool's row: K's part and
    V's part after it, each whole 128-lane tiles."""
    tiles = lambda n: -(-n // 128) * 128
    return tiles(head_dim), tiles(head_dim) + tiles(v_head_dim)


def grouped_rows(k: jax.Array, v: jax.Array) -> jax.Array:
    """The pool rows of ``k [..., Dk]`` and ``v [..., Dv]``: ``[..., W]``
    in ``k``'s dtype."""
    k_width, width = grouped_row_widths(k.shape[-1], v.shape[-1])
    fill = lambda a, n: jnp.pad(
        a, ((0, 0),) * (a.ndim - 1) + ((0, n - a.shape[-1]),))
    return jnp.concatenate(
        [fill(k, k_width), fill(v.astype(k.dtype), width - k_width)], -1)


def gather_grouped(pool: jax.Array, table: jax.Array, head_dim: int,
                   v_head_dim: int) -> tuple[jax.Array, jax.Array]:
    """The gathered form: each slot's rows through ``table [B, TP]``
    from ``pool [P, Hkv, S, W]``, as ``ops.kv_cache.attend_grouped``
    takes them: ``(k [B, TP * S, Hkv, Dk], v [B, TP * S, Hkv, Dv])``."""
    pages, hkv, page_size, width = pool.shape
    got = gather_pages(pool.reshape(pages, hkv * page_size, width), table)
    rows = got.reshape(table.shape[0], -1, hkv, page_size, width).transpose(
        0, 1, 3, 2, 4).reshape(table.shape[0], -1, hkv, width)
    k_width, _ = grouped_row_widths(head_dim, v_head_dim)
    return rows[..., :head_dim], rows[..., k_width:k_width + v_head_dim]


# Pages a grid step reads of one slot, ALL the K/V heads of each in one
# block (196,608 B at the published widths: 4 heads of 64 rows of 384
# bf16, contiguous in the pool, one copy), their K parts stacked in VMEM a
# head at a time into one ``[pages x page_size, Kw]`` K so that a step's
# scores are whole lane tiles. Timed on one v5e at
# ``serve-mimo-closed64-mixed``'s shape (64 slots of which 58 active, a
# 144-column table, 1,754 pages mapped = 345 MB of pool, 110,572 rows;
# PERF.md section 6, PR 35), ms a call at 2 / 4 / 8 / 16 / 32 pages a step:
# 1.672 / 1.146 / 0.987 / 0.989 / 1.029; the same grid copying and not
# computing 0.876 at 8. With one K/V head a block (49,152 B, four times
# the steps and the copies) 3.786 / 2.766 / 2.406 / 2.154 / 2.204, with
# two 2.411 / 1.726 / 1.472 / 1.400 / 1.564: a step's cost follows its
# count of copies, as PR 34's did. The gathered views and
# ``attend_grouped`` 11.628; the parent's two pools, gathered and re-laid
# out, 12.342. (All of these with the heads unrolled; with the heads in
# a loop, as committed, 1.303 / 1.044 / 1.009 at 4 / 8 / 16.)
GROUPED_PAGES_PER_STEP = 8


def grouped_kernel_accepts(group_heads: int, head_dim: int, v_head_dim: int,
                           page_size: int) -> bool:
    """Whether :func:`grouped_decode_attention`'s tiles fit: K and V
    heads of at least a 128-lane tile each (under one a row is mostly
    filling, and the gathered form reads less), a K/V head's query heads
    whole 8-row tiles, a page whole 16-row tiles (bf16's) so that pages
    stack for free."""
    return (min(head_dim, v_head_dim) >= 128 and group_heads % 8 == 0
            and page_size % 16 == 0)


def _grouped_kernel(fetch_ref, mapped_ref, qpos_ref, q_ref, *refs, group: int,
                    page_size: int, k_width: int, scale: float):
    del fetch_ref  # the index maps' operand
    kv_refs = refs[:group]                      # [1, Hkv, S, W]: k | v
    o_ref, m_ref, l_ref, acc_ref = refs[group:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    at = (b * pl.num_programs(1) + j) * group
    mapped = [mapped_ref[at + g] > 0 for g in range(group)]

    @pl.when(functools.reduce(jnp.logical_or, mapped))
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, group * page_size), 1)
        ok = j * (group * page_size) + lane <= qpos_ref[b]
        for g in range(group):  # an unmapped page holds the last fetched
            ok &= mapped[g] | (lane // page_size != g)

        def head(h, carry):
            q = q_ref[0, h]                                 # [Gq, Kw]
            k = jnp.concatenate([r[0, h, :, :k_width] for r in kv_refs],
                                axis=0).astype(q.dtype)
            v = jnp.concatenate([r[0, h, :, k_width:] for r in kv_refs],
                                axis=0).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Gq, G * S]
            s = jnp.where(ok, s, _MASKED)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(q.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            return carry

        # A loop, not ``for h in range``: the body is lowered once. A
        # program lowers the kernel anew whether or not its compiled
        # code is cached, and with the heads unrolled (64 loads of a
        # ref) that cost the host 0.6 s a decode bucket, 7 s of the
        # cell's warm ``setup_s``, for 0.06 ms a call (PERF.md section
        # 6, PR 35).
        jax.lax.fori_loop(0, q_ref.shape[1], head, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0, acc_ref[...] / l, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "v_head_dim", "scale", "pages_per_step", "interpret"))
def grouped_decode_attention(
    q: jax.Array,
    pool: jax.Array,
    table: jax.Array,
    q_pos: jax.Array,
    *,
    v_head_dim: int,
    scale: float | None = None,
    pages_per_step: int = GROUPED_PAGES_PER_STEP,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of one query a slot, a K/V head's query heads
    together, ``q [B, Hkv, Gq, Dk]`` at positions ``q_pos [B]`` (negative:
    the slot attends nothing and gets zeros), over the rows of ``pool [P,
    Hkv, S, W]`` (a row ``[k | zeros | v | zeros]``,
    :func:`grouped_row_widths`) that ``table [B, TP]`` maps (page ids,
    ``-1`` = unmapped; column ``c``'s rows hold positions ``c * S ..``).
    Returns ``[B, Hkv, Gq, v_head_dim]`` in ``q``'s dtype: what
    ``attend_grouped`` gives over :func:`gather_grouped`'s views, to
    rounding (online softmax). Scores are over ``sqrt(Dk)`` without a
    ``scale``.

    Jitted, so the layers of one program, each with a pool of its own,
    are one traced function lowered once. A grid step reads
    ``pages_per_step`` pages of a slot, every K/V head of each in one
    block (a table no such count divides is widened with unmapped
    columns); a step or a page past the slot's last mapped one names the
    page fetched last (no copy is issued) and a step with no mapped page
    skips its compute. The bytes moved follow the pages resident."""
    b, hkv, gq, dk = q.shape
    page_size, width = pool.shape[2:]
    k_width, row = grouped_row_widths(dk, v_head_dim)
    assert row == width, (row, width)
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    group = min(pages_per_step, table.shape[1])
    table = jnp.pad(table, ((0, 0), (0, -table.shape[1] % group)),
                    constant_values=-1)
    steps = table.shape[1] // group
    fetch, mapped = _fetch_plan(table, group)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, k_width - dk),))

    def page(g):
        def index(bi, ji, fetch_ref, mapped_ref, qpos_ref):
            return (fetch_ref[(bi * steps + ji) * group + g], 0, 0, 0)
        return index

    slot = lambda bi, ji, *_: (bi, 0, 0, 0)
    v_width = width - k_width
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, group=group, page_size=page_size,
                          k_width=k_width, scale=scale),
        name="grouped_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((1, hkv, gq, k_width), slot)] + [
                pl.BlockSpec((1, hkv, page_size, width), page(g))
                for g in range(group)],
            out_specs=pl.BlockSpec((1, hkv, gq, v_width), slot),
            scratch_shapes=[pltpu.VMEM((hkv, gq, 1), jnp.float32),
                            pltpu.VMEM((hkv, gq, 1), jnp.float32),
                            pltpu.VMEM((hkv, gq, v_width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gq, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(fetch, mapped.reshape(-1).astype(jnp.int32), q_pos.astype(jnp.int32),
      q, *[pool] * group)
    return out[..., :v_head_dim]
