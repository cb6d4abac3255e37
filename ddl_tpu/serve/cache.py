"""The serving KV caches on the tp mesh: the slot-major ring-buffer
pytree (:class:`KVCache` — the bit-exactness oracle, default) and the
PAGED block-table pool (:class:`PagedKVCache` + :class:`PagePool` —
``ServeConfig.page_size > 0``), which pools capacity across slots and
makes prefix reuse zero-copy (refcounted page sharing).

Slot-major state layout (one pytree, donated through every decode step
so serving is allocation-free after warmup):

- ``k``/``v [num_layers, slots, capacity, num_heads, head_dim]`` — the
  per-layer ring buffers of ``ops.kv_cache``, stacked layer-major so
  donation and sharding cover the whole cache with one leaf each.
- ``pos [slots, capacity]`` — the absolute token position each row
  holds, shared by all layers (every layer writes the same rows);
  ``ops.kv_cache.PAD_POS`` marks unwritten/stale rows. Attention masks
  on ``pos``, so evicting a finished sequence is pure host bookkeeping
  (the slot's rows become invisible the moment a new occupant's prefill
  resets them — no device work).

Tensor parallelism: under the Megatron column sharding
(``models.partition.lm_param_specs``) each device computes k/v for its
LOCAL head subset, so the cache shards over the HEAD dim on the same
``TP_AXIS`` — cache residency per device drops tp-fold, the serving
twin of the training-side weight sharding. ``pos`` is head-free and
stays replicated.
"""

from __future__ import annotations

import dataclasses
import heapq

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models.transformer import LMSpec
from ..ops.kv_cache import PAD_POS, copy_prefix
from ..parallel.mesh import TP_AXIS


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """See module docstring. A pytree — jit/shard_map/donation ready."""

    k: jax.Array  # [L, S, C, H, D]
    v: jax.Array  # [L, S, C, H, D]
    pos: jax.Array  # [S, C] int32, PAD_POS = unwritten/stale

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    @property
    def slots(self) -> int:
        return self.k.shape[1]


def host_cache(
    spec: LMSpec, slots: int, capacity: int, dtype=np.float32
) -> KVCache:
    """Fresh host-side cache: zero k/v, every row's position PAD_POS
    (nothing attendable). The caller places it with
    ``multihost.put_tree(mesh, cache_specs(tp), host_cache(...))``."""
    shape = (spec.num_layers, slots, capacity, spec.num_heads, spec.head_dim)
    return KVCache(
        k=np.zeros(shape, dtype),
        v=np.zeros(shape, dtype),
        pos=np.full((slots, capacity), PAD_POS, np.int32),
    )


def copy_slot_prefix(
    dst: KVCache,
    src: KVCache,
    *,
    src_slot: jax.Array,
    dst_slot: jax.Array,
    n: jax.Array,
) -> KVCache:
    """Copy the first ``n`` ring rows (K/V of every layer + positions) of
    ``src_slot`` in ``src`` into ``dst_slot`` of ``dst`` — the pytree
    form of ``ops.kv_cache.copy_prefix``, and the device half of prefix
    reuse (``serve.prefix``): ``src`` and ``dst`` may be the SAME cache
    (retained-slot reuse) or two caches sharing capacity/spec (the
    dedicated prefix pool). Destination rows ``>= n`` reset to
    ``PAD_POS`` so nothing of the previous occupant beyond the copied
    prefix is ever attendable. All indices/lengths may be traced — one
    compiled program per (cache shapes) pair. Head-dim tp sharding is
    row-local, so the copy needs no collective inside ``shard_map``."""
    sk = lax.dynamic_slice_in_dim(src.k, src_slot, 1, axis=1)
    sv = lax.dynamic_slice_in_dim(src.v, src_slot, 1, axis=1)
    sp = lax.dynamic_slice_in_dim(src.pos, src_slot, 1, axis=0)
    dk = lax.dynamic_slice_in_dim(dst.k, dst_slot, 1, axis=1)
    dv = lax.dynamic_slice_in_dim(dst.v, dst_slot, 1, axis=1)
    rows = jnp.arange(dst.pos.shape[1])
    new_pos = jnp.where(rows < n, sp[0], PAD_POS)[None, :].astype(dst.pos.dtype)
    return KVCache(
        k=lax.dynamic_update_slice_in_dim(
            dst.k, copy_prefix(dk, sk, n, axis=2), dst_slot, axis=1
        ),
        v=lax.dynamic_update_slice_in_dim(
            dst.v, copy_prefix(dv, sv, n, axis=2), dst_slot, axis=1
        ),
        pos=lax.dynamic_update_slice_in_dim(dst.pos, new_pos, dst_slot, axis=0),
    )


def cache_specs(tensor_parallel: int) -> KVCache:
    """PartitionSpec pytree for the cache: k/v shard their HEAD dim over
    the tp axis (each device caches exactly the heads its column-sharded
    ``wq``/``wk``/``wv`` produce); ``pos`` replicated. All-``P()`` at
    tp=1, mirroring ``lm_param_specs``."""
    kv = (P(None, None, None, TP_AXIS, None)
          if tensor_parallel > 1 else P())
    return KVCache(k=kv, v=kv, pos=P())


# -- paged (block-table) layout ----------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """The PAGED serving cache: ONE shared K/V pool of fixed-size pages
    instead of per-slot worst-case rings. Capacity pools across slots —
    a slot holds exactly the pages its sequence needs, mapped through a
    host-side block table (``serve.engine``), so one long request no
    longer reserves ``capacity`` rows for every co-resident, and prefix
    reuse becomes page SHARING (refcounts, ``serve.prefix``) instead of
    row copies.

    - ``k``/``v [num_layers, num_pages, page_size, num_heads, head_dim]``
      — the pool, layer-major like :class:`KVCache` so donation/sharding
      cover it with one leaf each; head dim tp-sharded identically.
    - ``pos [num_pages, page_size]`` — the absolute position each pool
      row holds, shared by all layers; ``PAD_POS`` = unwritten. The
      free-list invariant (``PagePool``): every UNMAPPED page is fully
      ``PAD_POS`` (pages reset when their last reference drops), so a
      freshly mapped page can never leak its previous occupant's
      positions into the gathered attend view.
    - ``k_scale``/``v_scale [num_layers, num_pages, page_size,
      num_heads]`` — per-head fp32 dequantization scales, present ONLY
      when the pool stores int8 payloads (``ServeConfig.kv_dtype ==
      "int8"``, ISSUE 19): row ``r`` of head ``h`` dequantizes as
      ``k[..., r, h, :] * k_scale[..., r, h]``
      (``ops.kv_cache.dequantize_rows``). ``None`` (the fp32/bf16
      default) is an EMPTY pytree node — the tree flattens to exactly
      the three historical leaves, so every off-path program (specs,
      donation, HLO) is byte-identical to the pre-int8 pool. Scales
      travel WITH their pages through every page motion (CoW copy,
      cross-replica write, dump/load), so sharing, preemption and
      disagg hand-off stay bit-exact.
    """

    k: jax.Array  # [L, P, page, H, D] (fp32/bf16, or int8 when quantized)
    v: jax.Array  # [L, P, page, H, D]
    pos: jax.Array  # [P, page] int32, PAD_POS = unwritten
    k_scale: jax.Array | None = None  # [L, P, page, H] fp32, int8 pools only
    v_scale: jax.Array | None = None  # [L, P, page, H] fp32, int8 pools only

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]


def host_paged_cache(
    spec: LMSpec, num_pages: int, page_size: int, dtype=np.float32,
    *, kv_dtype: str | None = None
) -> PagedKVCache:
    """Fresh host-side paged pool: zero k/v, every row ``PAD_POS`` (the
    free-list invariant holds from birth). Placed with
    ``multihost.put_tree(mesh, paged_cache_specs(tp), ...)``.
    ``kv_dtype="int8"`` stores int8 payloads plus per-head fp32 scale
    planes (initialized to 1.0 — dequant of the zero payload is an
    exact 0.0); ``None`` keeps the historical ``dtype`` pool with NO
    scale leaves."""
    shape = (spec.num_layers, num_pages, page_size,
             spec.num_heads, spec.head_dim)
    if kv_dtype is None:
        return PagedKVCache(
            k=np.zeros(shape, dtype),
            v=np.zeros(shape, dtype),
            pos=np.full((num_pages, page_size), PAD_POS, np.int32),
        )
    if kv_dtype != "int8":
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    return PagedKVCache(
        k=np.zeros(shape, np.int8),
        v=np.zeros(shape, np.int8),
        pos=np.full((num_pages, page_size), PAD_POS, np.int32),
        k_scale=np.ones(shape[:4], np.float32),
        v_scale=np.ones(shape[:4], np.float32),
    )


def paged_cache_specs(tensor_parallel: int, *,
                      kv_dtype: str | None = None) -> PagedKVCache:
    """PartitionSpec pytree for the paged pool: same head-dim tp
    sharding as :func:`cache_specs` (the pool's page axis is a memory
    axis, never a mesh axis); ``pos`` replicated. Int8 pools shard the
    scale planes over their HEAD axis (axis 3 of ``[L, P, page, H]``)
    exactly like the payloads they rescale — a page and its scales
    always live on the same tp member."""
    kv = (P(None, None, None, TP_AXIS, None)
          if tensor_parallel > 1 else P())
    if kv_dtype is None:
        return PagedKVCache(k=kv, v=kv, pos=P())
    sc = P(None, None, None, TP_AXIS) if tensor_parallel > 1 else P()
    return PagedKVCache(k=kv, v=kv, pos=P(), k_scale=sc, v_scale=sc)


def kv_row_bytes(spec: LMSpec, kv_dtype: str | None,
                 dtype=np.float32) -> int:
    """Bytes ONE pool row (K + V of every layer, scales included) costs
    on device — the byte-envelope arithmetic the int8 pool trades on:
    fp32 stores ``2 * L * H * D * 4`` bytes/row, int8 ``2 * L * H * (D
    + 4)`` (one int8 per element plus one fp32 scale per head), a
    ``4D / (D + 4)``x compression — 3.2x at head_dim 16, approaching 4x
    as heads widen. ``benchmarks/serve_bench.py`` sizes its int8 arm's
    ``num_pages`` from this so both arms spend the SAME byte budget and
    the free-page headroom becomes the measured win."""
    per_elem = 2 * spec.num_layers * spec.num_heads
    if kv_dtype is None:
        return per_elem * spec.head_dim * np.dtype(dtype).itemsize
    if kv_dtype != "int8":
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    return per_elem * (spec.head_dim + np.dtype(np.float32).itemsize)


def copy_page(
    pool: PagedKVCache,
    *,
    src_page: jax.Array,
    dst_page: jax.Array,
    n: jax.Array,
) -> PagedKVCache:
    """Copy the first ``n`` rows (K/V of every layer + positions) of
    ``src_page`` into ``dst_page`` — the ONLY copy on the paged prefix
    path: a hit whose depth is not page-aligned copy-on-writes the one
    PARTIAL boundary page (the new occupant must own it to write its own
    tail rows); every full page is shared by table mapping, zero-copy.
    Destination rows ``>= n`` reset to ``PAD_POS`` (the free-list
    invariant for the fresh page). All indices traced — one compiled
    program. Head-dim tp sharding is row-local: no collective needed.
    Int8 pools copy the per-head scale rows alongside their payload —
    a copied row dequantizes bit-identically to its source."""
    sk = lax.dynamic_slice_in_dim(pool.k, src_page, 1, axis=1)
    sv = lax.dynamic_slice_in_dim(pool.v, src_page, 1, axis=1)
    sp = lax.dynamic_slice_in_dim(pool.pos, src_page, 1, axis=0)
    dk = lax.dynamic_slice_in_dim(pool.k, dst_page, 1, axis=1)
    dv = lax.dynamic_slice_in_dim(pool.v, dst_page, 1, axis=1)
    rows = jnp.arange(pool.pos.shape[1])
    new_pos = jnp.where(rows < n, sp[0], PAD_POS)[None, :].astype(
        pool.pos.dtype
    )
    out = dataclasses.replace(
        pool,
        k=lax.dynamic_update_slice_in_dim(
            pool.k, copy_prefix(dk, sk, n, axis=2), dst_page, axis=1
        ),
        v=lax.dynamic_update_slice_in_dim(
            pool.v, copy_prefix(dv, sv, n, axis=2), dst_page, axis=1
        ),
        pos=lax.dynamic_update_slice_in_dim(
            pool.pos, new_pos, dst_page, axis=0
        ),
    )
    if pool.k_scale is None:
        return out
    sks = lax.dynamic_slice_in_dim(pool.k_scale, src_page, 1, axis=1)
    svs = lax.dynamic_slice_in_dim(pool.v_scale, src_page, 1, axis=1)
    dks = lax.dynamic_slice_in_dim(pool.k_scale, dst_page, 1, axis=1)
    dvs = lax.dynamic_slice_in_dim(pool.v_scale, dst_page, 1, axis=1)
    return dataclasses.replace(
        out,
        k_scale=lax.dynamic_update_slice_in_dim(
            pool.k_scale, copy_prefix(dks, sks, n, axis=2), dst_page,
            axis=1,
        ),
        v_scale=lax.dynamic_update_slice_in_dim(
            pool.v_scale, copy_prefix(dvs, svs, n, axis=2), dst_page,
            axis=1,
        ),
    )


def write_page(
    pool: PagedKVCache,
    *,
    dst_page: jax.Array,
    k_rows: jax.Array,
    v_rows: jax.Array,
    pos_rows: jax.Array,
    k_scale_rows: jax.Array | None = None,
    v_scale_rows: jax.Array | None = None,
) -> PagedKVCache:
    """Overwrite ``dst_page`` of the pool with caller-supplied rows (K/V
    of every layer + positions) — the receive half of the cross-replica
    KV hand-off (``serve.controller`` preemption): a preempted request's
    pages, fetched host-side from the SOURCE replica's pool
    (``engine.dump_slot_pages``), land bit-for-bit in freshly mapped
    pages of the destination's, so the resumed request's attend view is
    the source's to the bit. ``k_rows``/``v_rows`` are ``[L, 1, page, H,
    D]`` and ``pos_rows`` ``[1, page]`` — a whole page, including any
    ``PAD_POS`` tail, so the free-list invariant survives the write. The
    page id is traced — ONE compiled program covers every transfer;
    head-dim tp sharding is row-local (the rows arrive sharded the same
    way), no collective needed. Int8 pools receive the page's per-head
    ``*_scale_rows [L, 1, page, H]`` too — payload bytes without their
    scales would dequantize to the wrong values, so the hand-off moves
    both or neither (the engine's dump/load keeps them paired)."""
    out = dataclasses.replace(
        pool,
        k=lax.dynamic_update_slice_in_dim(pool.k, k_rows, dst_page, axis=1),
        v=lax.dynamic_update_slice_in_dim(pool.v, v_rows, dst_page, axis=1),
        pos=lax.dynamic_update_slice_in_dim(
            pool.pos, pos_rows, dst_page, axis=0
        ),
    )
    if k_scale_rows is None:
        return out
    return dataclasses.replace(
        out,
        k_scale=lax.dynamic_update_slice_in_dim(
            pool.k_scale, k_scale_rows, dst_page, axis=1
        ),
        v_scale=lax.dynamic_update_slice_in_dim(
            pool.v_scale, v_scale_rows, dst_page, axis=1
        ),
    )


class PagePool:
    """Host-side page allocator for the paged pool: free list, per-page
    refcounts, and admission RESERVATIONS — the whole "enough free
    pages" capacity story lives here, in plain Python (the device never
    sees allocation, only tables).

    - **Refcounts**: a page is held by every slot whose table maps it
      AND every prefix entry that registered it — zero-copy sharing is
      just ``incref``. The last ``decref`` frees the page; the caller
      (``serve.host._pages_freed``) then resets its ``pos`` rows to
      ``PAD_POS`` on device (the invariant ``PagedKVCache`` documents).
    - **Reservations**: the scheduler admits a request only when
      ``available`` (free minus already-promised) covers its worst case
      ``ceil((prompt + max_new) / page_size)`` minus the pages a prefix
      hit shares — so admission can never deadlock mid-decode, while
      capacity still pools ACROSS requests (the slot-major layout
      reserved ``capacity`` rows per slot unconditionally).
    - **Deterministic**: the free list pops lowest page id first, so a
      replayed request sequence maps identical pages — the paged twin
      of the prefix index's logical-clock LRU.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"page pool needs >= 1 page, got {num_pages}")
        self.num_pages = num_pages
        # Min-heap: alloc pops the LOWEST free id (deterministic maps),
        # frees push back in O(log P).
        self._free = list(range(num_pages))
        self.refs = np.zeros(num_pages, np.int32)
        self.reserved = 0

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def available(self) -> int:
        """Free pages not promised to an admitted request."""
        return len(self._free) - self.reserved

    @property
    def shared(self) -> int:
        """Pages held by more than one reader (slots + prefix entries)."""
        return int((self.refs >= 2).sum())

    def reserve(self, n: int) -> None:
        if n > self.available:
            raise RuntimeError(
                f"reserving {n} pages with only {self.available} available "
                f"({self.free} free, {self.reserved} already reserved) — "
                "admission must check availability first"
            )
        self.reserved += n

    def unreserve(self, n: int) -> None:
        if n > self.reserved:
            raise RuntimeError(
                f"unreserving {n} of {self.reserved} reserved pages"
            )
        self.reserved -= n

    def alloc(self) -> int:
        """Pop the lowest free page id at refcount 1. The caller owns
        the reservation bookkeeping (``serve.host._map_page``)."""
        if not self._free:
            raise RuntimeError("page pool exhausted (no free pages)")
        page = heapq.heappop(self._free)
        self.refs[page] = 1
        return page

    def incref(self, page: int) -> None:
        if self.refs[page] < 1:
            # Increfing a free page would resurrect it while it sits in
            # the free list — double allocation. Sharing is only legal
            # on live pages (a mapping slot or a registering entry
            # already holds one reference).
            raise RuntimeError(f"incref on free page {page}")
        self.refs[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; True when the page just freed (the
        caller must reset its device ``pos`` rows before reuse)."""
        if self.refs[page] < 1:
            raise RuntimeError(f"decref on free page {page}")
        self.refs[page] -= 1
        if self.refs[page] == 0:
            heapq.heappush(self._free, page)
            return True
        return False


# -- page groups side by side (models.hybrid) ---------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridKVCache:
    """The pools of a family whose layers keep different things, a leaf
    a LAYER in ``k`` (and ``v``, ``extra`` where the kind has them), of
    its group's page count: the global group keeps every row
    (``num_pages`` pages), the window group the last ``window`` rows
    (``num_window_pages`` pages).

    - A window layer keeps a K and a V pool, ``[pages, page_size,
      kv_heads * width]`` (a token's heads side by side in one row: whole
      128-lane tiles at the published widths, where a ``[.., 4, 192]``
      tail made the compiler copy the whole pool six times a decode tick
      to scatter into it), whose K and V rows differ in width.
    - A global layer keeps K and V in ONE pool, in ``k`` (its ``v`` is
      ``None``), the head before the row: ``[pages, kv_heads, page_size,
      W]``, a row ``[k | zeros | v | zeros]`` with each part filled to
      whole lane tiles (``ops.paged_attention.grouped_row_widths``: ``[k
      192 | 64 zeros | v 128]``, 384 values, at the published widths). A
      page is one contiguous block of whole tiles, a head's K and V lane
      slices of its rows: what the decode kernel
      ``grouped_decode_attention`` reads in place, one copy a page, and
      what every other program gathers from
      (``ops.paged_attention.gather_grouped``). One layout on every
      platform.
    - A latent layer keeps ONE pool, in ``k``, of the global group's
      pages: ``[pages, page_size, latent_pool_width]``, a token's
      compressed row ``[c | k_r]`` and zeros up to whole lane tiles; its
      ``v`` is ``None``.
    - A sparse layer keeps K and V in ONE pool of the global group's
      pages, in ``k`` (its ``v`` is ``None``), the head before the row:
      ``[pages, kv_heads, 2 x page_size, width]``, a head's K rows of a
      page followed by its V rows (one block of whole tiles whatever the
      head count, one copy for a kernel that reads both), and beside it,
      through the same block table, ``extra`` holds the selector's cache
      ``[pages x kv_heads x groups, width]``: the mean of each group of
      ``sparse_stride`` K rows (``ops.sparse_attention``).
    - A linear layer keeps no rows: its ``k`` and ``v`` are ``None`` and
      its ``extra`` is the STATE GROUP's leaf, ``[slots, heads, head_dim,
      v_head_dim]`` in fp32 whatever the compute dtype, not paged: zeroed
      by the prefill program of a slot's first chunk, carried from chunk
      to chunk and into decode (``ops.linear_attention``).

    A leaf a layer, so a layer's write is in place on its own donated
    buffer. No positions are stored: ``ops.kv_cache.ring_positions`` and
    a global row's logical index give them."""

    k: tuple
    v: tuple
    extra: tuple    # a layer's selector cache or state; else ``None``


def latent_pool_width(spec) -> int:
    """A latent layer's pool row: ``kv_lora_rank + rope_dim`` values
    rounded up to whole 128-lane tiles (576 -> 640 at the published
    widths). The chip keeps a ``[pages, page_size, 576]`` array with
    another axis minor-most (4.5 tiles would be padded), and a program
    that scatters rows into it and gathers pages from it copies the
    whole pool in and out to turn it, every tick; at 640 the pool is
    used as it lies, and 576 of 640 on the chip are 640 either way."""
    return -(-spec.latent_row // 128) * 128


def hybrid_cache(spec, num_pages: int, num_window_pages: int,
                 page_size: int, dtype, slots: int = 0) -> HybridKVCache:
    """Fresh zero pools (and states: ``slots`` of them a linear layer)
    on the default device."""
    from ..models.hybrid import GLOBAL, LATENT, LINEAR, SPARSE, WINDOW
    from ..ops.paged_attention import grouped_row_widths

    kind = lambda layer: spec.layer_kinds[layer]

    def pool(layer: int, width: int):
        if kind(layer) == LINEAR:
            return None
        if kind(layer) == SPARSE:
            return jnp.zeros((num_pages, spec.kv_heads(layer), 2 * page_size,
                              width), dtype)
        if kind(layer) == GLOBAL:
            _, row = grouped_row_widths(spec.head_dim, spec.v_head_dim)
            return jnp.zeros((num_pages, spec.kv_heads(layer), page_size,
                              row), dtype)
        pages = num_window_pages if kind(layer) == WINDOW else num_pages
        row = (latent_pool_width(spec) if kind(layer) == LATENT
               else spec.kv_heads(layer) * width)
        return jnp.zeros((pages, page_size, row), dtype)

    def extra(layer: int):
        if kind(layer) == LINEAR:
            return jnp.zeros((slots, spec.num_heads, spec.head_dim,
                              spec.v_head_dim), jnp.float32)
        if kind(layer) == SPARSE:
            rows = num_pages * spec.kv_heads(layer) * spec.selector.groups
            return jnp.zeros((rows, spec.head_dim), dtype)
        return None

    layers = range(spec.num_layers)
    return HybridKVCache(
        k=tuple(pool(i, spec.head_dim) for i in layers),
        v=tuple(pool(i, spec.v_head_dim) if kind(i) == WINDOW else None
                for i in layers),
        extra=tuple(extra(i) for i in layers))


def ring_columns(window: int, page_size: int) -> int:
    """Columns of a slot's window-group table: the most logical pages
    the last ``window`` rows can touch."""
    return (window - 2) // page_size + 2
