"""The engine of the ``models.hybrid`` family (global, window, latent,
linear and block-sparse attention layers): two page groups, one block
table a group a slot, a state group without pages, the same host API.

``serve.engine_cls(spec)`` is this class for a ``HybridSpec``; the
constructor's checks, the host state, scheduler, buckets, warm-up,
dispatch and the ``engine.*`` spans are the base class's. What differs
is what a spec of this family stands for: pool shapes
(:func:`serve.cache.hybrid_cache`), a prefill program and a decode
program over ``models.hybrid.apply_hybrid_paged``, and the host half of
the window group.

- **Global group**: the base class's ``pages`` / ``tables`` /
  ``table_len`` / ``reserved_for``, every row kept, decode programs
  bucketed on its page count. A global layer keeps K and V there in one
  pool with the head before the row (a row ``[k | zeros | v | zeros]``),
  a latent layer one pool of compressed rows, a sparse layer one pool
  with the head before the row (a head's K rows, then its V rows) and
  the selector's means beside it (``page_size`` is the selector's
  block).
- **State group**, where the pattern has a linear layer: one ``[slots,
  heads, head_dim, v_head_dim]`` fp32 array a layer, no pages, no host
  bookkeeping. A prefill program is told its slot: it starts from zeros
  at position 0 (so a readmitted slot starts clean, with no host write)
  and from the slot's state past it, which is how ``prefill_chunk``
  carries a prompt across chunks; a decode program moves the active
  slots' states on and leaves the others' as they are (a slot between
  two chunks of its prompt is one of them).
- **Window group**, where the pattern has a window layer (without one:
  no pool, no tables, no host work, ``ring`` 0): ``win_pages`` (a second
  ``PagePool``), ``win_tables [slots, R]`` a ring of columns (``ops.kv_cache.ring_rows``), logical
  page ``j`` in column ``j % R``. Before a row is written the pages whose
  last row the window has left are freed and the row's own page is
  mapped; a prefill block keeps only its last ``window`` rows beyond the
  call. The pool holds ``R`` pages a slot; admission reserves a slot's
  ``R``, and a freed page returns to its slot's reservation.

Programs keep the names ``jit_run_prefill_b<bucket>`` and
``jit_run_decode_p<pages>`` (the global group's bucket; the window
group's width is fixed). A prefill computes the last position's logits
alone, and a caller that asks for logits gets that row. Each program
returns, behind the sampled tokens in the one small array the host
waits for, the routed layers' ``(assigned, touched)``;
:attr:`last_counters` holds them for the scheduler's spans, beside the
host's own counts of a decode tick: ``win_pages`` (a pattern with window
layers), ``latent_rows`` (one with latent layers: the cached rows the
tick's active slots attend, summed over slots), and for a pattern with
sparse or linear layers ``kv_pages`` (pages that hold the active slots'
rows, a K/V head), ``sparse_pages`` (those a sparse layer attends: a
slot's own up to ``sparse_dense_len`` rows of context, ``sparse_topk``
past it) and ``state_slots`` (slots whose state the tick reads and
writes); a prefill of such a pattern counts ``sparse`` (1 where the
block's context passes ``sparse_dense_len``) and ``chunk`` (the block's
index in its prompt). The routed layers' counts are written where the
pattern has routed layers.

Weights are kept as handed over, cast once to ``compute_dtype`` when
the engine is built, never inside a call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import hybrid
from .cache import HybridKVCache, PagePool, hybrid_cache, ring_columns
from .engine import InferenceEngine, _arg

# What the family does not serve yet, by the config field that asks for
# it: each is refused by name when the engine is built.
UNSUPPORTED = (
    ("prefix cache", lambda c: c.prefix_slots > 0, "prefix_slots > 0"),
    ("speculation", lambda c: c.speculate_k > 0, "speculate_k > 0"),
    ("int8 pool", lambda c: c.kv_dtype is not None, "kv_dtype"),
    ("tensor parallelism", lambda c: c.tensor_parallel != 1,
     "tensor_parallel > 1"),
    ("contiguous cache", lambda c: c.page_size <= 0, "page_size = 0"),
)
HANDOFF = ("the hybrid family does not support the disaggregated hand-off "
           "(dump/load/alias of a slot's pages, preemption, prefill and "
           "decode roles): its page groups, a sparse layer's selector rows "
           "and a linear layer's recurrent state have no serialised form "
           "yet")


class HybridEngine(InferenceEngine):
    """See the module docstring."""

    handoff = False
    spec_type = hybrid.HybridSpec
    _init_params = staticmethod(hybrid.init_hybrid_params)
    refuses = tuple(
        (asked, f"the hybrid family does not support the {feature} "
                f"({field}) yet; it serves paged, tp 1, full-precision "
                "pools, no prefix cache, no speculation (a linear layer's "
                "state is of one slot's whole context: no prefix of it "
                "can be shared, no drafted row taken back)")
        for feature, asked, field in UNSUPPORTED)

    def _layout(self) -> None:
        cfg = self.config
        self.ring = self.num_window_pages = 0
        self.counts_latent_rows = bool(cfg.spec.layers_of(hybrid.LATENT))
        self.counts_moe = hybrid.MOE in cfg.spec.ffn_kinds
        self.has_state = bool(cfg.spec.layers_of(hybrid.LINEAR))
        self.sparse = cfg.spec.selector \
            if cfg.spec.layers_of(hybrid.SPARSE) else None
        if self.sparse and (
                self.page_size != self.sparse.block
                or cfg.prefill_chunk % self.sparse.stride):
            raise ValueError(
                f"a sparse layer's block is the pool's page: page_size "
                f"({self.page_size}) must be sparse_block "
                f"({self.sparse.block}), and a prefill chunk "
                f"({cfg.prefill_chunk}) whole groups of "
                f"{self.sparse.stride} rows")
        if not cfg.spec.layers_of(hybrid.WINDOW):
            return
        window = cfg.spec.window
        if cfg.capacity % window:
            raise ValueError(
                f"capacity ({cfg.capacity}) must be a multiple of the "
                f"window ({window}): a prefill block is cut into bands")
        # The window group's table width; its pool is one ring a slot,
        # all a slot can hold (admission reserves a ring, and a page the
        # window leaves goes back to its slot's reservation).
        self.ring = ring_columns(window, self.page_size)
        self.num_window_pages = cfg.slots * self.ring

    # -- state -------------------------------------------------------------

    def reset(self) -> None:
        cfg = self.config
        self._reset_host()  # the global group
        self.cache = None  # the old pools go before the new ones come
        self.cache = hybrid_cache(
            cfg.spec, self.num_pages, self.num_window_pages, self.page_size,
            np.dtype(cfg.compute_dtype or np.float32), cfg.slots)
        self.last_counters = {}
        if not self.ring:
            return
        self.win_pages = PagePool(self.num_window_pages)
        self.win_tables = np.full((cfg.slots, self.ring), -1, np.int32)
        # The logical page a column holds (-1: none).
        self.win_logical = np.full((cfg.slots, self.ring), -1, np.int64)
        self.win_reserved = np.zeros(cfg.slots, np.int64)
        self.win_held = np.zeros(cfg.slots, bool)  # admitted by reservation

    # -- page groups (host half) -------------------------------------------

    def can_admit(self, need: int) -> bool:
        """Each group the pattern has holds what a new slot reserves."""
        return super().can_admit(need) and (
            not self.ring or self.win_pages.available >= self.ring)

    def reserve_pages(self, slot: int, n: int) -> None:
        super().reserve_pages(slot, n)
        if not self.ring:
            return
        self.win_pages.reserve(self.ring)
        self.win_reserved[slot] += self.ring
        self.win_held[slot] = True

    def _pages_freed(self, pages) -> None:
        pass  # no stored positions to reset

    def release_slot(self, slot: int) -> None:
        super().release_slot(slot)
        if not self.ring:
            return
        for c in np.nonzero(self.win_tables[slot] >= 0)[0]:
            self._free_window(slot, int(c), keep=False)
        self.win_pages.unreserve(int(self.win_reserved[slot]))
        self.win_reserved[slot] = 0
        self.win_held[slot] = False

    def _free_window(self, slot: int, col: int, *, keep: bool) -> None:
        """Return a column's page to the pool; for a slot that goes on
        (``keep``) under an admission's reservation, to that too."""
        self.win_pages.decref(int(self.win_tables[slot, col]))
        self.win_tables[slot, col] = self.win_logical[slot, col] = -1
        if keep and self.win_held[slot]:
            self.win_pages.reserve(1)
            self.win_reserved[slot] += 1

    def _slide_window(self, slot: int, lo: int, hi: int) -> None:
        """Before a call writes rows ``lo..hi``: its queries see the rows
        above ``lo - window``, so every page whose last row lies at or
        under that is freed; then the pages of the rows kept beyond the
        call, the last ``window`` of them, are mapped."""
        ps, w = self.page_size, self.config.spec.window
        for c in np.nonzero(self.win_tables[slot] >= 0)[0]:
            if (int(self.win_logical[slot, c]) + 1) * ps - 1 <= lo - w:
                self._free_window(slot, int(c), keep=True)
        for j in range(max(lo, hi - w + 1) // ps, hi // ps + 1):
            c = j % self.ring
            if self.win_tables[slot, c] < 0:
                if self.win_reserved[slot] > 0:
                    self.win_reserved[slot] -= 1
                    self.win_pages.unreserve(1)
                elif self.win_pages.available < 1:
                    raise RuntimeError(
                        f"slot {slot}: window page group exhausted (free "
                        f"{self.win_pages.free}, reserved "
                        f"{self.win_pages.reserved})")
                self.win_tables[slot, c] = self.win_pages.alloc()
            self.win_logical[slot, c] = j

    @property
    def window_pages_in_use(self) -> int:
        return self.num_window_pages - self.win_pages.free

    def _no_handoff(self, *args, **kwargs):
        raise NotImplementedError(HANDOFF)

    dump_slot_pages = load_slot_pages = alias_slot_pages = _no_handoff

    def _place(self, params):
        """The tree as handed over, cast once to the compute dtype."""
        dtype = self.config.dtype()
        return jax.tree.map(lambda a: jnp.asarray(a, dtype or a.dtype), params)

    # -- compiled programs -------------------------------------------------

    def _win_table(self, rows=slice(None)):
        """The window group's tables as a program's argument, a host
        array; ``None`` (no argument at all) without the group."""
        return self.win_tables[rows] if self.ring else None

    def _win_layout(self, rows: int):
        """:meth:`_win_table`'s place in a program's packed layout."""
        return _arg(rows, self.ring) if self.ring else None

    def _forward(self, params, cache: HybridKVCache, tokens, **kw):
        spec = self.config.spec
        pools = {i: kept if kept[2] is not None else kept[:2]
                 for i, kept in enumerate(zip(cache.k, cache.v, cache.extra))}
        h, pools, counts = hybrid.apply_hybrid_paged(
            params, pools, tokens, spec, page_size=self.page_size,
            compute_dtype=self.config.dtype(),
            platform=self.mesh.devices.flat[0].platform, **kw)
        layers = range(spec.num_layers)
        return h, counts, HybridKVCache(
            k=tuple(pools[i][0] for i in layers),
            v=tuple(pools[i][1] for i in layers),
            extra=tuple(pools[i][2] if len(pools[i]) > 2 else None
                        for i in layers))

    def _prefill_paged_fn(self, bucket: int, all_rows: bool = False):
        """``(params, cache, packed) -> ([next, assigned, touched],
        logits [1, vocab] of the last real position, cache)``, ``packed``
        holding ``tokens [1, bucket], length, base, g_table [1,
        max_pages], w_table [1, R] or None, slot or None (a pattern with
        linear layers: whose state), request_id``. The family's one
        form: a caller that asks for logits (``all_rows``) gets that
        row."""
        if bucket in self._prefill_fns:
            return self._prefill_fns[bucket]

        def run(params, cache, tokens, length, base, g_table, w_table, slot,
                request_id):
            t = jnp.arange(bucket, dtype=jnp.int32)
            real = (t < length)[None, :]
            positions = jnp.where(real, base + t, -1)
            h, counts, cache = self._forward(
                params, cache, tokens, g_table=g_table, w_table=w_table,
                positions=positions, real=real,
                last=(base + length - 1)[None], base=base, slot=slot)
            last = lax.dynamic_slice_in_dim(h[0], length - 1, 1, axis=0)
            logits = hybrid.head_logits(params, last)
            nxt = self._sample(logits[0], request_id, base + length)
            return jnp.concatenate([nxt[None], counts]), logits, cache

        fn = self._program("prefill", bucket, f"run_prefill_b{bucket}", run,
                           self._prefill_layout(
                               bucket, _arg(1, self.max_pages),
                               self._win_layout(1),
                               _arg() if self.has_state else None))
        self._prefill_fns[bucket] = fn
        return fn

    def _decode_paged(self, pages: int):
        """``(params, cache, packed) -> ([next [S], assigned, touched],
        logits [S, vocab], cache)``, ``packed`` holding ``last_tokens
        [S], lengths [S], request_ids [S], active [S], g_table [S,
        pages], w_table [S, R] or None``. A slot that is not active
        writes nothing and counts nothing."""
        if pages in self._decode_paged_fns:
            return self._decode_paged_fns[pages]

        def run(params, cache, last_tokens, lengths, request_ids, active,
                g_table, w_table):
            positions = jnp.where(active, lengths, -1)
            h, counts, cache = self._forward(
                params, cache, last_tokens[:, None], g_table=g_table,
                w_table=w_table, positions=positions[:, None],
                real=active[:, None], last=positions)
            logits = hybrid.head_logits(params, h[:, 0])
            nxt = jax.vmap(self._sample)(logits, request_ids, lengths + 1)
            return jnp.concatenate([nxt, counts]), logits, cache

        slots = self.config.slots
        fn = self._program("decode", pages, f"run_decode_p{pages}", run,
                           self._decode_layout(_arg(slots, pages),
                                               self._win_layout(slots)))
        self._decode_paged_fns[pages] = fn
        return fn

    # -- host API: the base class's bracket, through its three hooks -------

    def _prefill_where(self, slot: int, base: int, t: int) -> tuple:
        where = super()._prefill_where(slot, base, t)
        if self.ring:
            self._slide_window(slot, base, base + t - 1)
        self._block = (base, t)
        return where + (self._win_table(slice(slot, slot + 1)),
                        slot if self.has_state else None)

    def _decode_where(self, lengths, active, _pages) -> tuple:
        """Each active slot's window (where the pattern has one) slides
        too: the pages it has left are freed, the new row's mapped."""
        fn, where = super()._decode_where(lengths, active, _pages)
        if _pages is None and self.ring:
            for s in np.nonzero(active)[0]:
                at = int(lengths[s])
                self._slide_window(int(s), at, at)
        return fn, where + (self._win_table(),)

    def _counted(self, kind: str, counts, lengths=None, active=None) -> None:
        self.last_counters = {"moe_assigned": int(counts[0])} \
            if self.counts_moe else {}
        if kind == "prefill":
            if self.sparse or self.has_state:
                base, t = self._block
                chunk = self.config.prefill_chunk
                self.last_counters.update(
                    chunk=base // chunk if chunk else 0,
                    sparse=int(bool(self.sparse)
                               and base + t > self.sparse.dense_len))
            return
        if self.counts_moe:
            self.last_counters["moe_touched"] = int(counts[1])
        if self.sparse or self.has_state:
            self.last_counters.update(self._tick_pages(lengths[active]))
        if self.ring:
            self.last_counters["win_pages"] = self.window_pages_in_use
        if self.counts_latent_rows:
            # each active slot's query attends rows 0 .. its length
            self.last_counters["latent_rows"] = int(
                lengths[active].sum() + active.sum())

    def _tick_pages(self, lengths) -> dict:
        """A decode tick's page counts from its active slots' lengths
        (each attends rows ``0 .. length``), a K/V head of one sparse
        layer: the pages that hold them, and those attended."""
        out = {"state_slots": len(lengths)} if self.has_state else {}
        if not self.sparse:
            return out
        sel, heads = self.sparse, self.config.spec.kv_heads_global
        held = lengths.astype(np.int64) // sel.block + 1
        read = np.where(lengths < sel.dense_len, held, sel.topk)
        return dict(out, kv_pages=int(held.sum()) * heads,
                    sparse_pages=int(read.sum()) * heads)


def engine_cls(spec) -> type[InferenceEngine]:
    """The engine class that serves ``spec``'s family."""
    return HybridEngine if isinstance(spec, hybrid.HybridSpec) \
        else InferenceEngine
