"""KV-cache primitives: ring-buffer append + position-masked attend.

The serving half of the sharded-mesh story (ddl_tpu.serve): a trained
decoder LM answers autoregressively, which means every generated token
re-attends the whole history — recomputing it per step is O(T^2) per
token. The standard fix is a **KV cache**: each layer's post-RoPE k and
pre-projection v rows are written once and re-read on every later step.

This module is the op layer only — pure functions usable inside
``shard_map`` (the same contract as ``parallel.collectives``); the cache
*pytrees* (contiguous slot-major AND the paged block-table pool), their
tp sharding and their donation policy live in ``ddl_tpu.serve.cache``.

Design decisions:

- **Ring buffer, not concat**: the cache is a fixed ``[B, C, H, D]``
  buffer updated in place (``.at[rows].set``) — under jit with donated
  buffers the decode step allocates nothing and its shape never changes,
  so ONE compiled program serves a request from first token to last
  (a growing concat would recompile per length). Writes wrap modulo the
  capacity ``C`` (:func:`append_rows` takes pre-wrapped row indices from
  the caller), which is what makes the buffer a *ring*.
- **Positions travel with the rows**: a ``pos [B, C]`` int32 array holds
  each row's ABSOLUTE token position (``PAD_POS`` where the row is
  unwritten or stale). Attention masks on ``pos``, never on the row
  index, so (1) causal masking is exact whatever order rows were
  written in, (2) a reused slot's stale rows are invisible until
  overwritten — eviction is free, (3) a wrapped ring degrades to an
  exact sliding window over the last ``C`` positions, and (4) RoPE's
  decode-time extrapolation (positions far past training length) needs
  no separate plumbing — the q position is just large.
- **Same numerics as the training oracle**: :func:`attend` is
  ``ring.full_attention``'s einsum/softmax written against a cache —
  fp32 scores, the same ``-1e30`` mask constant, output in ``v``'s
  dtype — so incremental decode logits can be pinned against full-
  forward ``apply_lm`` at tight tolerance (tests/test_serve.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Sentinel position for unwritten/stale cache rows: attend() masks
# k rows with pos > q_pos, and no real query position reaches int32 max,
# so a PAD_POS row can never be attended. (Stale k/v VALUES may remain
# in a reused slot's buffer — masking on position makes them invisible
# without touching the buffer.)
PAD_POS = jnp.iinfo(jnp.int32).max

_MASKED = -1e30  # ring.py's mask constant: keeps exp(s - max) NaN-free


def append_rows(cache: jax.Array, new: jax.Array, rows: jax.Array) -> jax.Array:
    """Write ``new [B, T, ...]`` into ``cache [B, C, ...]`` at per-slot
    row indices ``rows [B, T]`` (int32, already wrapped modulo ``C`` by
    the caller — ``serve.cache.write_rows`` owns the ring arithmetic).
    In-place under jit when ``cache`` is donated. Row indices within one
    slot must be distinct (they are: consecutive positions of one
    sequence); out-of-range indices are a scatter no-op per XLA's
    clamp-free scatter semantics — callers pass wrapped rows, never
    relying on that."""
    return jax.vmap(lambda c, n, r: c.at[r].set(n))(cache, new, rows)


def copy_prefix(
    dst: jax.Array, src: jax.Array, n: jax.Array, *, axis: int = 1
) -> jax.Array:
    """Rows ``[0, n)`` along ``axis`` take ``src``'s values; the rest keep
    ``dst``'s — the slot-to-slot prefix-reuse gather behind the serving
    prefix cache (``serve.prefix``): admitting a request whose prompt
    shares a cached prefix becomes "copy the prefix's K/V rows, prefill
    only the tail" instead of recomputing the prefix. ``n`` may be a
    traced scalar (ONE compiled program covers every hit length — the
    fixed-shape discipline of :func:`append_rows`). Rows are valid for
    the new occupant because causal attention makes row ``r`` of a
    prefix depend only on tokens ``0..r`` — identical by construction
    when the first ``n`` tokens match."""
    c = dst.shape[axis]
    mask = (jnp.arange(c) < n).reshape((c,) + (1,) * (dst.ndim - axis - 1))
    return jnp.where(mask, src, dst)


# -- paged (block-table) layout ----------------------------------------------
#
# The paged pool (serve.cache.PagedKVCache) replaces per-slot contiguous
# rings with one shared ``[pages, page_size, ...]`` pool plus a per-slot
# int32 block table of page indices (``-1`` = unmapped). These three
# helpers are the whole device-side contract:
#
# - logical row ``r`` of a slot lives in pool page ``table[r // page_size]``
#   at offset ``r % page_size`` (:func:`table_rows` flattens that to a
#   ``[num_pages * page_size]`` row index, mapping unmapped/out-of-reach
#   rows OUT OF BOUNDS so scatters drop them — the same drop discipline
#   offset prefill already relies on);
# - reads gather whole pages through the table (:func:`gather_pages`) and
#   positions gather alongside with ``PAD_POS`` where the table is
#   unmapped (:func:`table_positions`), so :func:`attend` runs UNCHANGED
#   on the gathered view: positions still travel with rows, masking and
#   eviction semantics are exactly the contiguous ring's. Pages appear in
#   table order = logical order, and masked padding contributes exactly 0
#   to the fp32 softmax/einsum, so a page-count-bucketed attend is
#   bitwise equal to the contiguous attend over the same history
#   (verified on this XLA:CPU before building; pinned in
#   tests/test_serve_paged.py).
#
# **When a view is gathered, and when it is not** (ISSUE 31). The gathered
# view is the general form: any number of queries a slot, the int8 pool's
# dequantised rows, any widths, any backend. A dense DECODE program on a
# TPU (one query a slot, a pool that is not int8, ``head_dim`` whole
# 128-lane tiles and the local heads whole 8-row tiles) gathers nothing:
# ``ops.paged_attention.paged_decode_attention`` walks the same table,
# reads each mapped page where it lies in the stack and applies
# :func:`attend`'s rule (``k_pos <= q_pos``, from the page's own row of
# the positions) in the kernel. ``models.transformer.apply_lm_paged``
# chooses at trace time from the shapes; the pool, its layout and every
# writer here are the same for both.
#
# **Layer offset** (ISSUE 29). The dense pool's K/V leaves are STACKED,
# ``[L, pages, page_size, ...]``, and a forward touches layer ``i`` of
# them without ever taking ``pool[i]`` out (a 134-MB copy a layer on the
# chip, and another to put it back): :func:`write_rows_flat` and
# :func:`gather_pages` take ``layer=i`` and address the stack through
# its flat views — row ``i * pages * page_size + flat`` of ``[L * pages
# * page_size, ...]``, page ``i * pages + max(table, 0)`` of ``[L *
# pages, page_size, ...]`` (the reshapes merge leading axes only: free,
# and untouched by a tp spec on the heads). The flat rows and the table
# stay per-layer quantities (:func:`table_rows` knows no layer), and a
# DROPPED row — anything outside ``[0, pages * page_size)`` — goes to
# ``L * pages * page_size``, out of bounds of the WHOLE stack: adding
# the layer's offset to it would land it in row 0 of layer ``i + 1``.


def table_rows(
    table: jax.Array, logical: jax.Array, page_size: int, num_pages: int
) -> jax.Array:
    """Flat pool row indices for per-slot LOGICAL rows ``logical [B, T]``
    through block table ``table [B, TP]`` (int32 page ids, ``-1`` =
    unmapped). Rows whose page is unmapped or beyond the table reach
    (``logical >= TP * page_size`` — callers signal "drop this write"
    that way) map to ``num_pages * page_size``: out of bounds, so the
    scatter drops them."""
    tp = table.shape[1]
    page = logical // page_size
    pid = jnp.take_along_axis(table, jnp.clip(page, 0, tp - 1), axis=1)
    ok = (logical >= 0) & (page < tp) & (pid >= 0)
    return jnp.where(ok, pid * page_size + logical % page_size,
                     num_pages * page_size)


def gather_pages(pool: jax.Array, table: jax.Array,
                 layer: int | None = None) -> jax.Array:
    """Per-slot contiguous K/V view ``[B, TP * page_size, ...]`` gathered
    from ``pool [pages, page_size, ...]`` through ``table [B, TP]``.
    Unmapped (``-1``) entries clamp to page 0 — their VALUES are live
    data of some other slot, which is exactly why masking happens on
    :func:`table_positions`' ``PAD_POS``, never on the gathered values.

    With ``layer=i`` the pool is the STACKED ``[L, pages, page_size,
    ...]`` array and the view is layer ``i``'s, gathered straight from
    the stack viewed as ``[L * pages, page_size, ...]`` at pages ``i *
    pages + max(table, 0)`` — the values ``gather_pages(pool[i],
    table)`` gives, without the copy of ``pool[i]``."""
    pages = jnp.maximum(table, 0)
    if layer is not None:
        num_layers, num_pages = pool.shape[:2]
        pool = pool.reshape((num_layers * num_pages,) + pool.shape[2:])
        pages = layer * num_pages + pages
    g = pool[pages]  # [B, TP, page, ...]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def table_positions(pos: jax.Array, table: jax.Array) -> jax.Array:
    """Positions travelling with the gathered rows: ``pos [pages,
    page_size]`` through ``table [B, TP]`` -> ``[B, TP * page_size]``,
    ``PAD_POS`` wherever the table is unmapped — the gathered twin of
    the contiguous cache's ``pos`` rows, so :func:`attend` masks the
    paged view exactly as it masks the ring."""
    g = jnp.where((table >= 0)[..., None], pos[jnp.maximum(table, 0)],
                  PAD_POS)
    return g.reshape(g.shape[0], -1)


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-head int8 quantization of fresh K/V rows
    ``x [..., D]`` -> ``(q int8 [..., D], scale fp32 [...])`` — the
    write half of the int8 KV pool (ISSUE 19, ``ServeConfig.kv_dtype``).
    One absmax scale per HEAD VECTOR (the trailing ``D`` axis): ``scale
    = amax / 127`` (1.0 for an all-zero row, so dequant stays finite and
    exact), values rounded to nearest and clipped to ``[-127, 127]``.
    Per-head scaling keeps the quantizer LOCAL to a head: each tp
    shard holds whole heads, so quantizing needs no cross-shard
    reduction and a stored (payload, scale) pair round-trips
    bit-identically through any dump/load hand-off at its own tp.
    Quantization happens in fp32 regardless of compute dtype (a bf16
    amax would move stored bytes between precision policies)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize_rows(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`quantize_rows` for the gathered attend view:
    ``q int8 [..., D]`` times its per-head ``scale [...]``, multiplied
    in fp32 (exact — int8 payloads and fp32 scales are both fp32-
    representable) then cast to the attend's compute ``dtype``."""
    return (q.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(dtype)


def write_rows_flat(pool: jax.Array, new: jax.Array, flat: jax.Array,
                    layer: int | None = None) -> jax.Array:
    """Write ``new [B, T, ...]`` into ``pool [pages, page_size, ...]``
    at FLAT row indices ``flat [B, T]`` (from :func:`table_rows`). All
    slots scatter into the ONE shared pool — distinct rows are the
    allocator's invariant (disjoint pages per slot; shared prefix pages
    are never written while shared). Out-of-bounds rows drop.

    With ``layer=i`` the pool is the STACKED ``[L, pages, page_size,
    ...]`` array, ``flat`` still indexes ONE layer's ``pages *
    page_size`` rows, and the write lands in layer ``i`` through the
    stack's flat view at row ``i * pages * page_size + flat``: one
    in-place scatter under donation, where ``pool.at[i].set(
    write_rows_flat(pool[i], ...))`` copies the layer out and back. A
    row outside ``[0, pages * page_size)`` is a DROPPED row and maps to
    ``L * pages * page_size`` — out of bounds of the whole stack, never
    offset into the next layer. No other layer's rows are touched."""
    lead = 2 if layer is None else 3
    rows = math.prod(pool.shape[:lead])
    if layer is not None:
        per_layer = rows // pool.shape[0]
        flat = jnp.where((flat >= 0) & (flat < per_layer),
                         layer * per_layer + flat, rows)
    out = pool.reshape((rows,) + pool.shape[lead:]).at[
        flat.reshape(-1)
    ].set(new.reshape((-1,) + new.shape[2:]))
    return out.reshape(pool.shape)


def attend(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    scale: float | None = None,
) -> jax.Array:
    """Causal attention of fresh queries against a cache.

    ``q [B, T, H, D]`` at absolute positions ``q_pos [B, T]``;
    ``k_cache``/``v_cache [B, C, H, D]`` whose row c holds the token at
    absolute position ``k_pos[b, c]`` (``PAD_POS`` = unwritten/stale).
    Masks ``k_pos <= q_pos`` — exact causal attention over whatever
    subset of history the cache holds, independent of row order.
    fp32 scores/softmax, output in ``v_cache``'s dtype (the
    ``ring.full_attention`` numerics contract)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache).astype(jnp.float32) * scale
    mask = k_pos[:, None, None, :] <= q_pos[:, None, :, None]  # [B,1,T,C]
    s = jnp.where(mask, s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cache.dtype), v_cache)


def attend_grouped(
    q: jax.Array,
    k_view: jax.Array,
    v_view: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    window: int | None = None,
    sink: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Attention of fresh queries against a cache view whose K/V heads
    are fewer than the query heads and whose K and V rows differ in
    width: ``q [B, T, Hq, Dk]``, ``k_view [B, C, Hkv, Dk]``, ``v_view
    [B, C, Hkv, Dv]`` -> ``[B, T, Hq, Dv]``. Query head ``h`` reads K/V
    head ``h // (Hq / Hkv)``.

    A key at ``k_pos[b, c]`` counts for the query at ``q_pos[b, t]``
    when ``0 <= k_pos <= q_pos`` and, with ``window``, ``k_pos > q_pos -
    window`` (the query's own position is one of the ``window``).
    ``sink [Hq]`` adds one learned logit a query head to the softmax's
    denominator only, so a row's weights sum to less than one. Scores
    are times ``scale``, over ``sqrt(Dk)`` without one. fp32 scores and
    softmax, the same mask constant as :func:`attend`."""
    b, t, hq, dk = q.shape
    hkv = k_view.shape[2]
    qg = q.reshape(b, t, hkv, hq // hkv, dk)
    s = jnp.einsum("bthgd,bchd->bhgtc", qg, k_view).astype(jnp.float32)
    s = s / math.sqrt(dk) if scale is None else s * scale
    kp, qp = k_pos[:, None, :], q_pos[:, :, None]             # [B, T, C]
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask[:, None, None], s, _MASKED)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, hkv, hq // hkv, 1, 1)
        m = jnp.maximum(m, sk)
    e = jnp.exp(s - m)
    den = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sk - m)
    p = (e / den).astype(v_view.dtype)
    out = jnp.einsum("bhgtc,bchd->bthgd", p, v_view)
    return out.reshape(b, t, hq, v_view.shape[-1])


# -- window page group: a ring of table columns ------------------------------
#
# A slot's window-group table has R columns; logical page ``j`` (rows
# ``j * page_size ..``) lives in column ``j % R``. R pages cover more
# than a window plus a page, so the logical pages that intersect the
# window never share a column, and the column's logical page follows
# from the last position written: no position is stored with a row.


def ring_rows(table: jax.Array, positions: jax.Array, page_size: int,
              num_pages: int) -> jax.Array:
    """Flat pool rows of ``positions [B, T]`` through the ring table
    ``table [B, R]``; a negative position or an unmapped column maps out
    of bounds, so the scatter drops the write."""
    r = table.shape[1]
    at = jnp.maximum(positions, 0)
    pid = jnp.take_along_axis(table, (at // page_size) % r, axis=1)
    ok = (positions >= 0) & (pid >= 0)
    return jnp.where(ok, pid * page_size + at % page_size,
                     num_pages * page_size)


def ring_positions(last: jax.Array, columns: int,
                   page_size: int) -> jax.Array:
    """The position each row of the gathered ring view ``[B, R *
    page_size]`` holds once ``last [B]`` is the last position written:
    column ``c`` holds the newest logical page ``j <= last // page_size``
    with ``j % R == c``. Rows of a page not yet reached come out
    negative, rows ahead of ``last`` above it, rows of a page the window
    has left at or under ``last - window``: the mask drops all three."""
    lp = (last // page_size)[:, None]                         # [B, 1]
    c = jnp.arange(columns, dtype=jnp.int32)[None, :]
    j = lp - (lp - c) % columns                               # [B, R]
    pos = j[..., None] * page_size + jnp.arange(page_size, dtype=jnp.int32)
    return pos.reshape(last.shape[0], -1)
