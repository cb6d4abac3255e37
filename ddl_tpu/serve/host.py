"""The host half of a serve engine, written once: the checked config,
block tables, reservations, prefix paging and the bucket ladders.

:class:`EngineHost` is the base class of every engine the control plane
drives: :class:`~ddl_tpu.serve.engine.InferenceEngine`, through it
:class:`~ddl_tpu.serve.hybrid_engine.HybridEngine`, and the device-free
:class:`~ddl_tpu.serve.sim.CostModelEngine`. The control plane reads
``pages``, ``tables``, ``table_len``, ``reserved_for``, ``page_copies``
and ``prefix`` as attributes of the engine, and every admission, growth,
release and prefix decision is plain Python on those numpy arrays: the
twin replays a real fleet's decisions because it runs this code, not a
copy of it.

The bookkeeping touches a device in two places; an engine fills in a
hook for each (both do nothing here):

- :meth:`EngineHost._pages_freed`: pages whose last reference went (the
  dense engine resets their stored positions to ``PAD_POS``);
- :meth:`EngineHost._copy_tail_page`: the copy-on-write of a prefix
  hit's partial boundary page (the dense engine's one copy program; the
  twin's hit is only counted, in ``page_copies``, here).

An engine's own stays with it: pools and programs, the contiguous
(``page_size = 0``) cache and its prefix pool, the hybrid engine's
window group, the twin's ``rows`` and virtual time.
"""

from __future__ import annotations

import numpy as np

from .cache import PagePool, kv_row_bytes
from .prefix import PrefixIndex


class EngineHost:
    """See the module docstring. A subclass calls :meth:`_configure`
    from its constructor and :meth:`_reset_host` from its ``reset``."""

    # The attributes ``engine_iface.ServeEngine`` lists; the scheduler
    # sets the hooks (``ledger_hook``: ``engine._LedgeredProgram``).
    kind = "real"
    handoff = True  # a slot's pages can be dumped, loaded and aliased
    last_counters: dict = {}
    prefix: PrefixIndex | None = None
    pool = None  # the contiguous cache's prefix pool, where there is one
    compile_hook = None
    ledger_hook = None
    # What this engine alone refuses of a ServeConfig, checked before
    # what every engine refuses: rules like ``serve.engine.REFUSED``'s.
    refuses: tuple = ()

    def _configure(self, config, params=None, placed_params=None) -> None:
        """Refuse a config this engine (``refuses``) or any engine
        (``ServeConfig.check``) cannot serve, then hold it with its page
        geometry (all zero for the contiguous cache)."""
        if params is not None and placed_params is not None:
            raise ValueError(
                "pass params (host tree, placed here) OR placed_params "
                "(an already-placed tree to share), not both"
            )
        self.page_size, self.max_pages, self.num_pages = \
            config.check(self.refuses)
        self.paged = self.page_size > 0
        self.config = config
        self.last_attend_width = config.capacity  # until a paged decode

    def _reset_host(self) -> None:
        """Fresh host state: the allocator, the block tables and the
        prefix index rebuilt as one unit (an index entry without its
        pages, or vice versa, would be corruption by construction). An
        engine's ``reset`` makes its device pools beside it."""
        cfg = self.config
        if self.paged:
            self.pages = PagePool(self.num_pages)
            self.tables = np.full((cfg.slots, self.max_pages), -1, np.int32)
            self.table_len = np.zeros(cfg.slots, np.int64)
            self.reserved_for = np.zeros(cfg.slots, np.int64)
            self.page_copies = 0  # CoW tail copies — the zero-copy pin
        if cfg.prefix_slots > 0:
            self.prefix = PrefixIndex(
                cfg.prefix_slots,
                on_evict=(lambda e: self._release_pages(e.pages))
                if self.paged else None)

    # -- the two device hooks ------------------------------------------------

    def _pages_freed(self, pages: list[int]) -> None:
        """``pages`` just returned to the free list."""

    def _copy_tail_page(self, src_page: int, dst_page: int, n: int) -> None:
        """The first ``n`` rows of ``src_page`` become ``dst_page``'s."""

    # -- bucket ladders and prices -------------------------------------------

    def prefill_bucket(self, prompt_len: int) -> int:
        """Smallest power-of-two bucket >= max(prompt_len, 8), capped at
        capacity — a handful of compiled programs cover every length."""
        if not 1 <= prompt_len <= self.config.capacity:
            raise ValueError(
                f"prompt length {prompt_len} outside [1, capacity="
                f"{self.config.capacity}]"
            )
        b = 8
        while b < prompt_len:
            b *= 2
        return min(b, self.config.capacity)

    def decode_page_bucket(self, pages: int) -> int:
        """The page-count bucket ladder: smallest power of two >=
        ``pages``, capped at the table width — a handful of compiled
        decode programs cover every residency."""
        b = 1
        while b < pages:
            b *= 2
        return min(b, self.max_pages)

    def _prefill_block(self, prompt, base: int, _bucket: int | None):
        """One prompt block checked against the capacity: ``(tokens
        int32 [t], t, bucket)``, the bucket ``_bucket`` when forced."""
        prompt = np.asarray(prompt, np.int32)
        t = int(prompt.shape[0])
        if base < 0 or base + t > self.config.capacity:
            raise ValueError(
                f"prefill block [base={base}, base+{t}) outside cache "
                f"capacity {self.config.capacity}"
            )
        bucket = self.prefill_bucket(t) if _bucket is None else _bucket
        assert bucket >= t, (bucket, t)
        return prompt, t, bucket

    def _decode_bucket(self, lengths, active, _pages: int | None) -> int:
        """Map any page an active slot's next row crosses into
        (consuming its admission reservation — this can never find the
        pool empty), then the page-count bucket that covers the widest
        ACTIVE table, or ``_pages`` when forced (warmup, no state
        moves). A mid-prefill slot's wider table truncates harmlessly:
        it is inactive, its writes drop and its outputs are discarded."""
        if _pages is None:
            widest = 1
            for s in np.nonzero(active)[0]:
                self._ensure_rows(int(s), int(lengths[s]) + 1)
                widest = max(widest, int(self.table_len[s]))
            _pages = self.decode_page_bucket(widest)
        self.last_attend_width = _pages * self.page_size
        return _pages

    def handoff_bytes(self, n_pages: int) -> int:
        """Device bytes ``n_pages`` dumped/loaded pages represent,
        priced by the ``serve.cache.kv_row_bytes`` oracle (int8 pools:
        payloads + scale planes — the compressed wire size the
        ``handoff_bytes_total{path=}`` counters publish)."""
        dtype = np.dtype(self.config.compute_dtype or np.float32)
        return int(n_pages) * self.page_size * kv_row_bytes(
            self.config.spec, self.config.kv_dtype, dtype
        )

    # -- paged page management -----------------------------------------------

    def pages_needed(self, rows: int) -> int:
        """Worst-case page count for ``rows`` resident rows."""
        return -(-rows // self.page_size)

    def reserve_pages(self, slot: int, n: int) -> None:
        """Admission promise: hold ``n`` pages of headroom for ``slot``
        so its prefill chunks and decode page-boundary crossings can
        never find the pool empty mid-flight. Consumed page-by-page as
        the slot actually maps them; the remainder releases with the
        slot (``release_slot``)."""
        self.pages.reserve(n)
        self.reserved_for[slot] += n

    def can_admit(self, need: int) -> bool:
        """Whether ``need`` pages can be reserved for a new slot now."""
        return self.pages.available >= need

    def reclaim_pages(self, need: int) -> bool:
        """Evict zero-ref prefix entries (LRU-first) until ``need``
        pages can be admitted, dropping their page references — shared
        pages whose last holder was the entry return to the free list.
        Only entries whose eviction would actually FREE a page are
        candidates (an entry whose every page is still mapped by a live
        slot frees nothing now — evicting it would just burn future
        hits; its pages free naturally when the slots finish). False
        when no candidate can reach the target."""

        def frees(e) -> bool:
            return any(int(self.pages.refs[int(p)]) == 1
                       for p in set(e.pages))

        while not self.can_admit(need):
            if self.prefix is None or self.prefix.evict_lru(frees) is None:
                return False
        return True

    def _map_page(self, slot: int) -> int:
        """Append one freshly allocated page to ``slot``'s block table,
        consuming the slot's admission reservation when it has one
        (direct engine use — tests, warmup — allocates unreserved)."""
        if self.reserved_for[slot] > 0:
            self.reserved_for[slot] -= 1
            self.pages.unreserve(1)
        elif self.pages.available < 1:
            raise RuntimeError(
                f"slot {slot}: page pool exhausted (free "
                f"{self.pages.free}, reserved {self.pages.reserved}) — "
                "admission must reserve before the slot grows"
            )
        page = self.pages.alloc()
        t = int(self.table_len[slot])
        self.tables[slot, t] = page
        self.table_len[slot] = t + 1
        return page

    def _ensure_rows(self, slot: int, rows: int) -> None:
        """Map pages so logical rows ``[0, rows)`` of ``slot`` are
        writable. Reach is bounded by the table width (validated at
        submit — ``scheduler._validate``)."""
        need = self.pages_needed(rows)
        if need > self.max_pages:
            raise ValueError(
                f"slot {slot}: {rows} rows need {need} pages, table "
                f"reach is {self.max_pages} pages "
                f"({self.config.capacity} rows)"
            )
        while int(self.table_len[slot]) < need:
            self._map_page(slot)

    def _release_pages(self, pages) -> None:
        """Drop one reference per page; those hitting zero return to
        the free list and go to :meth:`_pages_freed`."""
        freed = [int(p) for p in pages if self.pages.decref(int(p))]
        if freed:
            self._pages_freed(freed)

    def release_slot(self, slot: int) -> None:
        """Free ``slot``'s residency: drop its page references (shared
        prefix pages survive on the entry's reference), clear its block
        table, and return any unused admission reservation — eviction
        and completion are the same host bookkeeping, exactly like the
        contiguous path's pos masking."""
        self._need_pages("release_slot")
        n = int(self.table_len[slot])
        pages = [int(p) for p in self.tables[slot, :n]]
        self.tables[slot, :] = -1
        self.table_len[slot] = 0
        left = int(self.reserved_for[slot])
        if left:
            self.pages.unreserve(left)
            self.reserved_for[slot] = 0
        self._release_pages(pages)

    def _need_pages(self, what: str) -> None:
        if not self.paged:
            raise RuntimeError(
                f"{what} needs the paged KV layout (page_size > 0) — the "
                "contiguous ring has no slot-independent pages"
            )

    def alias_slot_pages(self, dst_slot: int, src_slot: int,
                         rows: int) -> int:
        """Make ``dst_slot`` a zero-copy alias of ``src_slot``'s table
        covering logical rows ``[0, rows)`` — the draft-LANE setup of
        speculative decoding (ISSUE 15, ``serve.speculate``): the lane
        writes its draft token's K/V row through the SHARED pages and
        attends the shared history, so one batched decode call verifies
        k drafts with zero copies and zero new programs. Maps any page
        ``src_slot`` still needs first (consuming ITS admission
        reservation — the lane itself reserves nothing), then increfs
        each page into the lane's table. The lane is torn down with the
        ordinary ``release_slot`` (pure decref — the source's own
        references keep every page live). Returns the aliased page
        count."""
        self._need_pages("alias_slot_pages")
        if int(self.table_len[dst_slot]) or int(self.reserved_for[dst_slot]):
            raise RuntimeError(
                f"alias_slot_pages into non-empty slot {dst_slot} "
                "(lanes must be free slots)"
            )
        self._ensure_rows(src_slot, rows)
        n = int(self.table_len[src_slot])
        for i in range(n):
            page = int(self.tables[src_slot, i])
            self.pages.incref(page)
            self.tables[dst_slot, i] = page
        self.table_len[dst_slot] = n
        return n

    # -- prefix cache over pages ---------------------------------------------

    def prefix_fetch(self, entry_id: int, n: int, slot: int) -> int:
        """HIT: make the first ``n`` rows of entry ``entry_id`` resident
        in decode ``slot`` and pin the entry (refcount) until the caller
        releases it — LRU pressure can never free a prefix a live
        request was admitted from. Returns the number of K/V rows
        DEVICE-COPIED for the hit: the entry's full pages map straight
        into the slot's block table (incref — ZERO copies); only when
        ``n`` is not page-aligned does the one PARTIAL boundary page
        copy-on-write into a freshly mapped page (returns ``n %
        page_size`` — the ``page_copies`` counter and the scheduler's
        trace events assert exactly this bound). An engine with a
        contiguous cache copies ``n`` rows in its own override."""
        e = self.prefix.entry(entry_id)
        ps = self.page_size
        shared, tail = n // ps, n % ps
        if int(self.table_len[slot]):
            raise RuntimeError(
                f"prefix_fetch into non-empty slot {slot} (admission "
                "maps shared pages into a fresh table only)"
            )
        for i in range(shared):
            page = int(e.pages[i])
            self.pages.incref(page)
            self.tables[slot, i] = page
        self.table_len[slot] = shared
        if tail:
            # The entry always covers the boundary page: its token
            # coverage is a page multiple >= any match depth n.
            dst = self._map_page(slot)
            self._copy_tail_page(int(e.pages[shared]), dst, tail)
            self.page_copies += 1
        self._pin(entry_id)
        return tail

    def _pin(self, entry_id: int) -> None:
        self.prefix.touch(entry_id)
        self.prefix.acquire(entry_id)

    def prefix_release(self, entry_id: int) -> None:
        self.prefix.release(entry_id)

    def prefix_store(self, prompt, slot: int) -> bool:
        """REGISTRATION: index ``prompt`` and make its freshly prefilled
        rows ``0..p-1`` resident for future hits. Must run before the
        slot's first decode write (the scheduler does — row ``p`` is
        still stale here). False = registration skipped (index full of
        pinned entries, or the prompt spans no full page).

        The entry takes a reference on each of the slot's FULL prompt
        pages (the partial last page stays slot-private — decode is
        about to write into it), so registration moves zero K/V bytes
        and the pages are shared from that moment on. The slot's own
        reference keeps every donated page live until it finishes, so an
        eviction racing this insert can never free them. An engine with
        a contiguous cache snapshots the rows in its own override."""
        prompt = np.asarray(prompt, np.int32)
        full = int(prompt.shape[0]) // self.page_size
        if full < 1:
            return False
        pages = [int(p) for p in self.tables[slot, :full]]
        if self.prefix.insert(prompt[: full * self.page_size],
                              pages=pages) is None:
            return False
        for page in pages:
            self.pages.incref(page)
        return True
