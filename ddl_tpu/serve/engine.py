"""The inference engine: a jitted ``(prefill, decode)`` pair over the
serving mesh.

This is the device half of the serving subsystem (the host half — slot
admission, eviction, batching policy — is ``serve.scheduler``). Two
compiled programs cover a request's whole life:

- **prefill**: one block of a request's prompt (padded to a power-of-two
  bucket so a handful of programs serve every length) runs through
  ``transformer.apply_lm_cached`` in a single forward, writing rows
  ``base..base+t-1`` of its slot and sampling sequence element
  ``base + t`` from the last real position's logits. ``base == 0`` with
  ``t == p`` is classic whole-prompt prefill; a nonzero ``base`` resumes
  after a prefix-cache copy (``serve.prefix``) or an earlier CHUNK of
  the same prompt (chunked prefill — the scheduler interleaves prompt
  chunks with decode ticks so a long prompt cannot stall every active
  slot). The slot's stale ``pos`` rows are reset to ``PAD_POS`` from
  ``base`` on — never below it, which is exactly what keeps copied
  prefix rows and earlier chunks attendable — so a reused slot can
  never leak its previous occupant's history. Padded bucket-tail writes
  redirect out of bounds (the scatter drops them), so a bucket
  overhanging the capacity at a late ``base`` can never wrap onto live
  prefix rows.
- **decode**: ONE token per active slot, batched over all slots in a
  single fixed-shape program — each slot embeds its last token at its
  own absolute position (``rope`` takes per-slot ``[S, 1]`` positions),
  appends one cache row, attends its own history, and samples. The
  cache pytree is donated, so steady-state decode allocates nothing.
  Free slots ride along (fixed shapes = one compiled program) writing
  ``PAD_POS`` rows that no later occupant can attend.

Sampling is greedy at ``temperature == 0``, else temperature softmax
(optionally top-k-truncated) sampled with a key derived ONLY from
``(seed, request_id, token_index)`` — never from the slot index or the
step counter — so a request's tokens are bit-identical whether it runs
alone or continuously batched with strangers at any arrival pattern
(the scheduler-parity pin, tests/test_serve.py).

**Prefix cache** (``prefix_slots > 0``): a dedicated pool — a second
KVCache pytree of ``prefix_slots`` slots, NEVER part of the decode
batch, so enabling the cache changes neither the decode program nor its
cost — holds registered prompt prefixes; ``serve.prefix.PrefixIndex``
(host trie + refcounted LRU) decides residency. Admission becomes: copy
the longest-hit rows pool→slot (one jitted, donated gather program —
``serve.cache.copy_slot_prefix``), then prefill only the tail at
``base = hit``. Registration is the mirror copy slot→pool right after a
prompt's prefill completes (before decode touches row ``p``). Copied
rows are bit-identical to the rows a fresh prefill would write, so the
determinism contract survives reuse exactly (pinned cache-on vs
cache-off in tests/test_serve.py).

**Paged KV pool** (``page_size > 0``; ISSUE 7 tentpole): the per-slot
rings become ONE shared ``[L, pages, page_size, H, D]`` pool plus a
host-side int32 block table per slot — attention gathers each slot's
pages back through the table (positions travel with pool rows, so
masking/eviction semantics are unchanged), writes route through it, and
the pool's capacity is POOLED across slots: admission is "enough free
pages" for ``prompt + max_new`` (host accounting, ``cache.PagePool``)
instead of a worst-case ``capacity`` reservation per slot. Decode
programs bucket on PAGE COUNT (powers of two capped at the table width)
so the per-token attend cost tracks actual residency. On this pool the
prefix cache is ZERO-COPY: registration donates the slot's full prompt
pages to the index entry (refcount, no snapshot), a hit maps those
pages into the new slot's table, and only a non-page-aligned hit
copy-on-writes the one partial boundary page (``page_copies`` counts
them — the zero-copy acceptance pin). The contiguous path is retained
as the bit-exactness ORACLE: paged decode is pinned bit-identical to
it, tokens and per-step logits, tp=1 and tp=2
(tests/test_serve_paged.py).

Tensor parallelism reuses the training plumbing wholesale: params
placed by ``models.partition.lm_param_specs``, the cache's head dim
sharded by ``serve.cache.cache_specs``, and the row-sharded matmul
outputs completed by ``collectives.tp_allreduce`` inside ``shard_map``
— serving tp=N is the training forward at tp=N, so a checkpoint from
ANY trained topology serves on any tp the heads divide by.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import transformer
from ..models.partition import lm_param_specs
from ..models.hybrid import HybridSpec
from ..models.transformer import LMSpec
from ..obs.trace import span
from ..ops.kv_cache import PAD_POS
from ..parallel import collectives as coll
from ..parallel import multihost
from ..parallel.mesh import TP_AXIS, donation_for, make_mesh
from .cache import (
    KVCache,
    PagedKVCache,
    PagePool,
    cache_specs,
    copy_page,
    copy_slot_prefix,
    host_cache,
    host_paged_cache,
    kv_row_bytes,
    paged_cache_specs,
    write_page,
)
from .prefix import PrefixIndex


def _named(fn, name: str):
    """``fn`` under ``name``: XLA calls a jitted function's program
    ``jit_<name>``, which is all a device trace shows of it. Prefill
    programs are ``run_prefill_b<bucket>`` (paged) / ``_c<bucket>``
    (contiguous), decode ``run_decode_p<pages>`` / ``run_decode_c``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _LedgeredProgram:
    """First-call AOT capture of one cached serve program for the
    collective ledger (ISSUE 20, obs.comms). Built ONLY when the
    engine's ``ledger_hook`` is attached at build time — without it the
    cache holds the bare jitted callable and the off path is unchanged
    by construction.

    Order matters: calling a jitted fn after a separate
    ``lower().compile()`` compiles the program TWICE (the jit call
    cache does not adopt an external AOT compile), so the wrapper
    compiles once at the first real call's arguments, hands the
    ``Compiled`` object to the hook (which fetches the optimized HLO
    text and publishes the ledger), and dispatches every call —
    including the first — through that same executable. ``Compiled``
    honors the jit's donation and accepts the host scalars the call
    sites pass, so the dispatch semantics are the jit's own. ``lower``
    delegates to the underlying jitted fn (the AOT probes in tests
    lower cached programs directly)."""

    __slots__ = ("_engine", "_kind", "_key", "_jfn", "_compiled")

    def __init__(self, engine, kind: str, key: int, jfn):
        self._engine = engine
        self._kind = kind
        self._key = key
        self._jfn = jfn
        self._compiled = None

    def lower(self, *args, **kwargs):
        return self._jfn.lower(*args, **kwargs)

    def __call__(self, *args):
        c = self._compiled
        if c is None:
            c = self._jfn.lower(*args).compile()
            hook = self._engine.ledger_hook
            if hook is not None:
                hook(self._kind, self._key, c)
            self._compiled = c
        return c(*args)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving topology + sampling policy. ``slots`` is the continuous-
    batching width (concurrent sequences); ``capacity`` bounds each
    slot's prompt + generated length (the KV ring's row count).

    ``prefix_slots`` sizes the prefix-cache pool (0 = off): dedicated
    contiguous pool slots by default, or the maximum RESIDENT PREFIX
    ENTRY count in paged mode (entries hold refcounted page lists, not
    slots). ``prefill_chunk`` (0 = off; else a power of two >= 8, ONE
    more bucket — not per-length programs) splits prompts into fixed
    chunks the scheduler interleaves with decode ticks;
    ``prefill_budget`` caps prefill tokens per scheduler tick (0 = one
    chunk per tick, the maximum-interleaving default; requires
    chunking, and must be >= the chunk so every tick can make progress).

    ``page_size > 0`` switches the KV cache to the PAGED block-table
    layout (``serve.cache.PagedKVCache``): one shared pool of
    ``num_pages`` fixed-size pages replaces the per-slot rings —
    capacity pools across slots (admission becomes "enough free pages"
    instead of a worst-case ``capacity`` reservation per slot), prefix
    hits share pages zero-copy by refcount, and decode programs bucket
    on PAGE COUNT so attention cost tracks actual residency, not
    ``capacity``. ``capacity`` still bounds one slot's reach
    (``capacity // page_size`` block-table entries). ``num_pages = 0``
    defaults to ``slots * capacity / page_size`` — the slot-major
    memory envelope, no pooling savings but drop-in. The contiguous
    path (``page_size = 0``, the default) is retained as the
    bit-exactness oracle: paged decode is PINNED bit-identical to it
    (tests/test_serve_paged.py).

    ``kv_dtype = "int8"`` (paged layout only; ISSUE 19) stores the pool
    as int8 payloads plus per-head fp32 scales
    (``serve.cache.PagedKVCache.k_scale``): rows quantize on page write
    and dequantize in the gathered attend view
    (``ops.kv_cache.quantize_rows``/``dequantize_rows``), cutting pool
    bytes ``4 * head_dim / (head_dim + 4)``-fold (3.2x at head_dim 16)
    so the SAME byte budget holds more pages — more admission headroom,
    more FREE-slot draft lanes for speculation. Scales travel WITH
    their pages through ``dump_slot_pages``/``load_slot_pages`` (as
    ``(payload, scale)`` pairs the host side passes through opaquely),
    so preempt/adopt, crash requeue and the disagg hand-off all move
    the compressed bytes and resume bit-exactly. ``None`` (default)
    keeps the fp32/bf16 pool — the compiled programs are byte-identical
    to pre-int8 builds (HLO-pinned in tests/test_precision.py)."""

    spec: LMSpec | HybridSpec = LMSpec()
    slots: int = 4
    capacity: int = 256
    tensor_parallel: int = 1
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0  # 0 = full vocab (temperature > 0 only)
    seed: int = 0
    compute_dtype: str | None = None  # None = fp32; "bfloat16" = MXU path
    prefix_slots: int = 0  # prefix-cache pool width; 0 = off
    prefill_chunk: int = 0  # chunked-prefill block; 0 = whole-prompt
    prefill_budget: int = 0  # prefill tokens per scheduler tick; 0 = all
    page_size: int = 0  # paged KV layout: rows per page; 0 = contiguous
    num_pages: int = 0  # paged pool size; 0 = slots * capacity / page_size
    kv_dtype: str | None = None  # "int8" = quantized paged pool; None = full
    # Speculative decoding (ISSUE 15, serve.speculate): k > 0 drafts up
    # to k tokens per active slot per tick and verifies them through
    # FREE SLOTS of the one batched decode call (zero new programs —
    # the draft lanes alias the speculating slot's pages). Greedy-
    # accept needs the greedy target (temperature 0), the paged layout
    # (lane tables are page aliases) and slots >= 2 (somewhere for a
    # lane to ride). method: "ngram" (prompt + generated lookup) or
    # "prompt" (prompt-only lookup). k = 0 is the byte-identical
    # pre-speculation tick (HLO-pinned in tests/test_serve_speculate).
    speculate_k: int = 0
    speculate_method: str = "ngram"

    def dtype(self):
        return None if self.compute_dtype is None else jnp.dtype(self.compute_dtype)


def _load_host_params(path, spec, init=transformer.init_lm_params):
    """Params-only host tree from any trainer checkpoint: the template
    is shapes-only (``jax.eval_shape`` — no arrays are initialized just
    to be overwritten)."""
    from ..utils.checkpoint import load_params

    template = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), spec))
    host, _, _ = load_params(path, template)
    return host


class InferenceEngine:
    """Owns the placed params, the cache state, and the compiled
    program pair. ``params`` is a host pytree (e.g. a fresh init or a
    ``utils.checkpoint.load_params`` result); ``None`` seeds a random
    init — the smoke/demo path. ``placed_params`` instead SHARES an
    already-placed device tree from another engine on an identical
    mesh (the multi-replica router's one-checkpoint contract,
    ISSUE 8) — no re-placement, no transient duplicate copy; safe
    because no compiled program donates the params argument.

    This class is one implementation of the control-plane engine
    contract (:class:`~ddl_tpu.serve.engine_iface.ServeEngine`); the
    device-free twin (:class:`~ddl_tpu.serve.sim.CostModelEngine`,
    ``kind == "sim"``) is the other."""

    kind = "real"
    handoff = True  # a slot's pages can be dumped, loaded and aliased
    # What the last program counted beyond its tokens, as attributes for
    # the scheduler's span around the call; the dense family counts
    # nothing.
    last_counters: dict = {}

    # What a family's engine brings (``serve.hybrid_engine`` is the
    # second; ``serve.engine_cls(spec)`` picks the class): its spec
    # type, a fresh host tree, its layout (``_layout``) and how a host
    # tree becomes the resident one (``_place``), beside its pools
    # (``reset``) and programs.
    spec_type = LMSpec
    _init_params = staticmethod(transformer.init_lm_params)

    def __init__(self, config: ServeConfig, params=None, *,
                 placed_params=None):
        if params is not None and placed_params is not None:
            raise ValueError(
                "pass params (host tree, placed here) OR placed_params "
                "(an already-placed tree to share), not both"
            )
        tp = config.tensor_parallel
        spec = config.spec
        if not isinstance(spec, self.spec_type):
            raise ValueError(
                f"{type(self).__name__} serves a {self.spec_type.__name__}, "
                f"got a {type(spec).__name__}: serve.engine_cls(spec) is "
                "the class of the spec's family"
            )
        if tp < 1:
            raise ValueError(f"tensor_parallel must be >= 1, got {tp}")
        if tp > 1:
            if spec.num_heads % tp:
                raise ValueError(
                    f"tensor_parallel needs num_heads ({spec.num_heads}) "
                    f"divisible by tp ({tp})"
                )
            if spec.d_ff % tp:
                raise ValueError(
                    f"tensor_parallel needs d_ff ({spec.d_ff}) "
                    f"divisible by tp ({tp})"
                )
        if config.slots < 1 or config.capacity < 2:
            raise ValueError(
                f"need slots >= 1 and capacity >= 2, got "
                f"{config.slots} / {config.capacity}"
            )
        if not 0 <= config.top_k <= spec.vocab:
            raise ValueError(
                f"top_k must be in [0, vocab={spec.vocab}], got "
                f"{config.top_k}"
            )
        if config.prefix_slots < 0:
            raise ValueError(
                f"prefix_slots must be >= 0, got {config.prefix_slots}"
            )
        ck = config.prefill_chunk
        if ck and (ck < 8 or ck & (ck - 1)):
            # Power-of-two >= 8: a chunk is ITS OWN prefill bucket (plus
            # the smaller buckets any final partial chunk already uses),
            # keeping the compiled-program count logarithmic.
            raise ValueError(
                f"prefill_chunk must be 0 or a power of two >= 8, got {ck}"
            )
        if config.prefill_budget:
            if not ck:
                raise ValueError(
                    "prefill_budget requires prefill_chunk (the budget "
                    "meters chunk interleaving; whole-prompt prefill "
                    "ignores it silently otherwise)"
                )
            if config.prefill_budget < ck:
                raise ValueError(
                    f"prefill_budget ({config.prefill_budget}) below "
                    f"prefill_chunk ({ck}) could never start a chunk"
                )
        # Paged-layout config (loud-ctor discipline, ISSUE 7 satellite):
        # a malformed page geometry is a config error here, never a
        # mid-run surprise.
        ps = config.page_size
        if ps < 0 or (ps and ps & (ps - 1)):
            raise ValueError(
                f"page_size must be 0 (contiguous) or a power of two, "
                f"got {ps} (pages tile the capacity and the row->page "
                "split is a shift/mask)"
            )
        if config.num_pages and not ps:
            raise ValueError(
                f"num_pages ({config.num_pages}) requires page_size > 0 "
                "(the contiguous layout has no page pool)"
            )
        if config.num_pages < 0:
            raise ValueError(f"num_pages must be >= 0, got {config.num_pages}")
        self.paged = ps > 0
        # Quantized-pool config (loud-ctor discipline): int8 storage is
        # a property of the PAGE pool — the contiguous ring is the bit-
        # exactness oracle and stays full-precision by definition.
        if config.kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {config.kv_dtype!r}"
            )
        if config.kv_dtype == "int8" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' needs the paged KV layout (page_size > "
                "0): quantized storage lives in the shared page pool; "
                "the contiguous ring is the full-precision oracle"
            )
        self.quantized = config.kv_dtype == "int8"
        # Speculation config (loud-ctor discipline): every requirement
        # is structural — a violated one could only surface as silently
        #-never-speculating or a mid-run lane failure.
        sk = config.speculate_k
        if sk < 0:
            raise ValueError(f"speculate_k must be >= 0, got {sk}")
        from .speculate import SPECULATE_METHODS

        if config.speculate_method not in SPECULATE_METHODS:
            raise ValueError(
                f"speculate_method must be one of "
                f"{', '.join(SPECULATE_METHODS)}, got "
                f"{config.speculate_method!r}"
            )
        if sk > 0:
            if not self.paged:
                raise ValueError(
                    f"speculate_k={sk} needs the paged KV layout "
                    "(page_size > 0): draft lanes verify through block-"
                    "table ALIASES of the speculating slot's pages, and "
                    "contiguous slot rings have no pages to alias"
                )
            if config.temperature > 0.0:
                raise ValueError(
                    f"speculate_k={sk} needs temperature=0 (greedy): "
                    "greedy-accept is what keeps speculative output "
                    "bit-identical to plain decode; sampled acceptance "
                    "is a different algorithm"
                )
            if config.slots < 2:
                raise ValueError(
                    f"speculate_k={sk} needs slots >= 2: drafts verify "
                    "through FREE slots of the batched decode, and a "
                    "1-slot batch has no lane to ride"
                )
        if self.paged:
            if config.capacity % ps:
                raise ValueError(
                    f"capacity ({config.capacity}) must be a multiple of "
                    f"page_size ({ps}) — the block table holds whole pages"
                )
            self.page_size = ps
            self.max_pages = config.capacity // ps  # block-table width
            self.num_pages = config.num_pages or config.slots * self.max_pages
            if self.num_pages < config.slots:
                raise ValueError(
                    f"num_pages ({self.num_pages}) below slots "
                    f"({config.slots}) — every admitted slot needs at "
                    "least one page; the pool could never fill the batch"
                )
        else:
            self.page_size = self.max_pages = self.num_pages = 0
        self.config = config
        # A 1-D tp mesh: serving has no data/sequence axis — the batch
        # dim is the slot dim, resident whole on every tp member.
        self.mesh = make_mesh(tp, axis=TP_AXIS)
        self._layout()
        if placed_params is not None:
            self.params = placed_params
        else:
            if params is None:
                params = self._init_params(
                    jax.random.PRNGKey(config.seed), spec
                )
            self.params = self._place(params)
        self._row_reduce = coll.tp_allreduce(TP_AXIS) if tp > 1 else None
        # Compile-activity hook (ISSUE 10, obs/memory.py): called as
        # ``hook(kind, key)`` at every DISTINCT program build — each
        # cached program serves exactly one shape signature, so builds
        # and XLA compiles are 1:1. None (the default) is a no-op; the
        # scheduler attaches a registry-backed hook when telemetry is
        # on, so the off path is unchanged.
        self.compile_hook = None
        # Collective-ledger hook (ISSUE 20, obs.comms): called as
        # ``hook(kind, key, compiled)`` once per distinct program at
        # its first real dispatch, with the AOT ``Compiled`` object
        # (the only handle the optimized HLO text hangs off). None
        # (the default) leaves every cached program a bare jitted
        # callable — no wrapper, no HLO fetch, the off path unchanged
        # by construction. The scheduler attaches it beside
        # ``compile_hook`` when a registry is on.
        self.ledger_hook = None
        # The width the LAST decode attended per slot (paged: the
        # page-count bucket's rows; contiguous: the fixed capacity) —
        # the paged-aware denominator of serve_flops_per_token.
        self.last_attend_width = config.capacity
        self._prefill_fns: dict[int, object] = {}
        self._decode_fn = None
        self._decode_paged_fns: dict[int, object] = {}
        self._copy_in = None  # pool slot -> cache slot (prefix hit)
        self._copy_out = None  # cache slot -> pool slot (registration)
        self._copy_page_fn = None  # paged CoW: partial tail page
        self._write_page_fn = None  # paged: cross-replica page hand-off
        self._reset_pages_fn = None  # paged: PAD_POS freed pages' pos
        self.pool: KVCache | None = None
        self.prefix: PrefixIndex | None = None
        self.reset()

    def _layout(self) -> None:
        """What the family lays out from the checked config, before
        params are placed and the pools made: here the partition specs
        of the params and of both cache layouts."""
        tp = self.config.tensor_parallel
        self._pspecs = lm_param_specs(self.config.spec, tp)
        self._cspecs = cache_specs(tp)
        if self.paged:
            self._pcspecs = paged_cache_specs(
                tp, kv_dtype=self.config.kv_dtype)

    @classmethod
    def from_checkpoint(cls, config: ServeConfig, path) -> "InferenceEngine":
        """Build an engine serving a checkpoint's params directly — no
        throwaway random init is ever placed (the constructor receives
        the loaded host tree). Same params-only contract as
        :meth:`load_params`."""
        return cls(config, params=_load_host_params(
            path, config.spec, cls._init_params))

    def _note_compile(self, kind: str, key: int) -> None:
        """One distinct program was just built (engine.__init__
        docstring for the hook contract)."""
        if self.compile_hook is not None:
            self.compile_hook(kind, key)

    def _ledgered(self, kind: str, key: int, jfn):
        """Wrap a freshly built jitted program for collective-ledger
        capture when the hook is attached; identity otherwise (the off
        path caches the bare jit — ``_LedgeredProgram`` docstring)."""
        if self.ledger_hook is None:
            return jfn
        return _LedgeredProgram(self, kind, key, jfn)

    def handoff_bytes(self, n_pages: int) -> int:
        """Device bytes ``n_pages`` dumped/loaded pages represent,
        priced by the ``serve.cache.kv_row_bytes`` oracle (int8 pools:
        payloads + scale planes — the compressed wire size the
        ``handoff_bytes_total{path=}`` counters publish)."""
        dtype = np.dtype(self.config.compute_dtype or np.float32)
        return int(n_pages) * self.page_size * kv_row_bytes(
            self.config.spec, self.config.kv_dtype, dtype
        )

    # -- state -------------------------------------------------------------

    def reset(self) -> None:
        """Fresh (empty) cache — every slot free, nothing attendable.
        The prefix pool and its host index reset TOGETHER (an index
        entry without its device rows, or vice versa, would be
        corruption by construction). Paged mode rebuilds the page pool,
        the block tables and the allocator as one unit for the same
        reason."""
        dtype = np.dtype(self.config.compute_dtype or np.float32)
        if self.paged:
            self.cache = multihost.put_tree(
                self.mesh, self._pcspecs,
                host_paged_cache(self.config.spec, self.num_pages,
                                 self.page_size, dtype,
                                 kv_dtype=self.config.kv_dtype),
            )
            self.pages = PagePool(self.num_pages)
            self.tables = np.full(
                (self.config.slots, self.max_pages), -1, np.int32
            )
            self.table_len = np.zeros(self.config.slots, np.int64)
            self.reserved_for = np.zeros(self.config.slots, np.int64)
            self.page_copies = 0  # CoW tail copies — the zero-copy pin
            if self.config.prefix_slots > 0:
                self.prefix = PrefixIndex(
                    self.config.prefix_slots,
                    on_evict=lambda e: self._release_pages(e.pages),
                )
            return
        self.cache = multihost.put_tree(
            self.mesh, self._cspecs,
            host_cache(self.config.spec, self.config.slots,
                       self.config.capacity, dtype),
        )
        if self.config.prefix_slots > 0:
            self.pool = multihost.put_tree(
                self.mesh, self._cspecs,
                host_cache(self.config.spec, self.config.prefix_slots,
                           self.config.capacity, dtype),
            )
            self.prefix = PrefixIndex(self.config.prefix_slots)

    # -- paged page management (host half) ---------------------------------

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
        pages are available, dropping their page references — shared
        pages whose last holder was the entry return to the free list.
        Only entries whose eviction would actually FREE a page are
        candidates (an entry whose every page is still mapped by a live
        slot frees nothing now — evicting it would just burn future
        hits; its pages free naturally when the slots finish). False
        when no candidate can reach the target."""

        def frees(e) -> bool:
            return any(int(self.pages.refs[int(p)]) == 1
                       for p in set(e.pages))

        while self.pages.available < need:
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
        """Drop one reference per page; pages hitting zero return to
        the free list AND get their device ``pos`` rows reset to
        ``PAD_POS`` (one batched scatter — the free-list invariant that
        lets a freshly mapped page join the gathered attend view with
        nothing attendable)."""
        freed = [p for p in pages if self.pages.decref(int(p))]
        while freed:
            batch, freed = freed[: self.max_pages], freed[self.max_pages:]
            ids = np.full(self.max_pages, self.num_pages, np.int32)
            ids[: len(batch)] = batch  # padding is out of bounds: dropped
            if self._reset_pages_fn is None:
                # dataclasses.replace keeps any scale leaves riding
                # along untouched — freed pages reset ONLY their pos
                # rows (stale payloads/scales are invisible behind
                # PAD_POS, exactly like the contiguous ring).
                self._reset_pages_fn = self._ledgered(
                    "pages_reset", 0,
                    jax.jit(
                        lambda cache, pages: dataclasses.replace(
                            cache, pos=cache.pos.at[pages].set(PAD_POS),
                        ),
                        donate_argnums=donation_for(self.mesh, 0),
                    ),
                )
                self._note_compile("pages_reset", 0)
            self.cache = self._reset_pages_fn(self.cache, jnp.asarray(ids))

    def release_slot(self, slot: int) -> None:
        """Free ``slot``'s residency: drop its page references (shared
        prefix pages survive on the entry's reference), clear its block
        table, and return any unused admission reservation — eviction
        and completion are the same host bookkeeping, exactly like the
        contiguous path's pos masking."""
        n = int(self.table_len[slot])
        pages = [int(p) for p in self.tables[slot, :n]]
        self.tables[slot, :] = -1
        self.table_len[slot] = 0
        left = int(self.reserved_for[slot])
        if left:
            self.pages.unreserve(left)
            self.reserved_for[slot] = 0
        self._release_pages(pages)

    def load_params(self, path) -> None:
        """Params-only checkpoint load (``utils.checkpoint.load_params``):
        accepts a trainer checkpoint from ANY topology — optimizer/step
        state is ignored if present and not required to exist."""
        self.params = self._place(_load_host_params(
            path, self.config.spec, self._init_params))

    def _place(self, params):
        """A host tree onto the mesh, as the programs take it."""
        return multihost.put_tree(self.mesh, self._pspecs, params)

    # -- sampling ----------------------------------------------------------

    def _sample(self, logits, request_id, token_index):
        """One token from one ``[vocab]`` logit row. The PRNG key folds
        in ONLY (seed, request_id, token_index): batch composition, slot
        assignment and arrival time cannot change a request's stream."""
        cfg = self.config
        if cfg.temperature <= 0.0:
            return jnp.argmax(logits).astype(jnp.int32)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(cfg.seed), request_id),
            token_index,
        )
        scaled = logits / cfg.temperature
        if cfg.top_k > 0:
            kth = jnp.sort(scaled)[-cfg.top_k]
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
        return jax.random.categorical(key, scaled).astype(jnp.int32)

    # -- compiled programs -------------------------------------------------

    def _shard_forward(self):
        """The cached forward both programs wrap — shape-generic over
        ``[B, T]`` token blocks: prefill hands it a ``[1, bucket]``
        slot slice, decode the ``[slots, 1]`` batch."""
        cfg = self.config

        def body(params, cache: KVCache, tokens, start, positions, rows=None):
            logits, k, v, pos = transformer.apply_lm_cached(
                params, tokens, cache.k, cache.v, cache.pos, cfg.spec,
                start=start, positions=positions, rows=rows,
                compute_dtype=cfg.dtype(), row_reduce=self._row_reduce,
            )
            return logits, KVCache(k=k, v=v, pos=pos)

        return body

    def _prefill_fn(self, bucket: int):
        """Compiled prefill for prompt blocks padded to ``bucket``
        tokens: ``(params, cache, tokens [1, bucket], length, base,
        slot, request_id) -> (next_token, logits [bucket, vocab],
        cache)``. ``base`` is the slot's position offset — 0 for a whole
        prompt, the copied-prefix length after a prefix-cache hit, the
        running offset for chunk 2+ of a chunked prefill. One program
        per bucket covers every ``(length, base)``."""
        if bucket in self._prefill_fns:
            return self._prefill_fns[bucket]
        cfg = self.config
        fwd = self._shard_forward()

        def shard_body(params, cache: KVCache, tokens, length, base, slot):
            # Slot slice: [L, 1, C, H, D] k/v + [1, C] pos. Stale pos
            # rows reset to PAD_POS from `base` on — rows BELOW base are
            # the copied prefix / earlier chunks and stay attendable;
            # everything at or beyond is the previous occupant's and
            # can never be attended (k/v values may remain — masking on
            # position makes them invisible).
            C = cache.pos.shape[1]
            old_pos = lax.dynamic_slice_in_dim(cache.pos, slot, 1, axis=0)
            sl = KVCache(
                k=lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1),
                v=lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1),
                pos=jnp.where(jnp.arange(C) < base, old_pos[0],
                              PAD_POS)[None, :].astype(jnp.int32),
            )
            t = jnp.arange(bucket, dtype=jnp.int32)
            real = t < length
            # Padded tail positions are PAD_POS and their WRITES
            # redirect to row C — out of bounds, which XLA scatter
            # DROPS — so a bucket overhanging the capacity at a late
            # base can never wrap onto live prefix rows, with no
            # sacrificial row and no edge case at base + length == C.
            positions = jnp.where(real, base + t, PAD_POS)[None, :]
            rows = jnp.where(real, (base + t) % C, C)[None, :]
            logits, sl = fwd(params, sl, tokens,
                             jnp.zeros((1,), jnp.int32), positions, rows)
            cache = KVCache(
                k=lax.dynamic_update_slice_in_dim(cache.k, sl.k, slot, axis=1),
                v=lax.dynamic_update_slice_in_dim(cache.v, sl.v, slot, axis=1),
                pos=lax.dynamic_update_slice_in_dim(
                    cache.pos, sl.pos, slot, axis=0
                ),
            )
            return logits[0], cache

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._pspecs, self._cspecs, P_(), P_(), P_(), P_()),
            out_specs=(P_(), self._cspecs),
            check_vma=False,
        )

        def run(params, cache, tokens, length, base, slot, request_id):
            logits, cache = shard(params, cache, tokens, length, base, slot)
            last = lax.dynamic_index_in_dim(
                logits, length - 1, axis=0, keepdims=False
            )
            # The sampled token is sequence element `base + length` of
            # this request — the token_index the PRNG key folds in (only
            # the block ending at the prompt's last token uses it; the
            # scheduler discards mid-prompt samples).
            nxt = self._sample(last, request_id, base + length)
            return nxt, logits, cache

        fn = self._ledgered(
            "prefill", bucket,
            jax.jit(_named(run, f"run_prefill_c{bucket}"),
                    donate_argnums=donation_for(self.mesh, 1)),
        )
        self._prefill_fns[bucket] = fn
        self._note_compile("prefill", bucket)
        return fn

    def _decode(self):
        """Compiled decode step: one token for every slot at once.
        ``(params, cache, last_tokens [S], lengths [S], request_ids [S],
        active [S]) -> (next_tokens [S], logits [S, vocab], cache)``."""
        if self._decode_fn is not None:
            return self._decode_fn
        fwd = self._shard_forward()

        def shard_body(params, cache, last_tokens, lengths, active):
            # Free slots still compute (fixed shapes = one program) but
            # write PAD_POS rows: invisible to any future occupant.
            positions = jnp.where(active, lengths, PAD_POS)[:, None]
            logits, cache = fwd(params, cache, last_tokens[:, None],
                                lengths, positions)
            return logits[:, 0], cache

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._pspecs, self._cspecs, P_(), P_(), P_()),
            out_specs=(P_(), self._cspecs),
            check_vma=False,
        )

        def run(params, cache, last_tokens, lengths, request_ids, active):
            logits, cache = shard(params, cache, last_tokens, lengths, active)
            # This step extends each sequence to length+1 tokens; the
            # sampled token's index is lengths + 1 (prefill sampled
            # index `length`, decode continues the same numbering).
            nxt = jax.vmap(self._sample)(logits, request_ids, lengths + 1)
            return nxt, logits, cache

        self._decode_fn = self._ledgered(
            "decode", 0,
            jax.jit(_named(run, "run_decode_c"),
                    donate_argnums=donation_for(self.mesh, 1)),
        )
        self._note_compile("decode", 0)
        return self._decode_fn

    # -- paged compiled programs -------------------------------------------

    def _paged_forward(self, params, pool: PagedKVCache, tokens, table,
                       *, positions, flat_rows):
        """The one ``apply_lm_paged`` call both paged programs trace:
        routes the pool's scale planes in (and the updated planes back
        out) when the pool is int8 — a STATIC branch on
        ``self.quantized``, so the full-precision programs are
        byte-identical to pre-int8 builds."""
        cfg = self.config
        if self.quantized:
            logits, k, v, pos, ks, vs = transformer.apply_lm_paged(
                params, tokens, pool.k, pool.v, pool.pos, table,
                cfg.spec, positions=positions, flat_rows=flat_rows,
                compute_dtype=cfg.dtype(), row_reduce=self._row_reduce,
                pool_k_scale=pool.k_scale, pool_v_scale=pool.v_scale,
            )
            return logits, PagedKVCache(k=k, v=v, pos=pos,
                                        k_scale=ks, v_scale=vs)
        logits, k, v, pos = transformer.apply_lm_paged(
            params, tokens, pool.k, pool.v, pool.pos, table, cfg.spec,
            positions=positions, flat_rows=flat_rows,
            compute_dtype=cfg.dtype(), row_reduce=self._row_reduce,
        )
        return logits, PagedKVCache(k=k, v=v, pos=pos)

    def _prefill_paged_fn(self, bucket: int):
        """Paged prefill for prompt blocks padded to ``bucket`` tokens:
        ``(params, pool, tokens [1, bucket], length, base,
        table [1, max_pages], request_id) -> (next_token,
        logits [bucket, vocab], pool)``. Same sampling/offset contract
        as the contiguous ``_prefill_fn`` — writes route through the
        slot's block table instead of a slot slice, padded tails map
        OUT OF BOUNDS (dropped), and the table is passed at its FULL
        width (prefill is matmul-bound; the page-count bucket ladder is
        the DECODE program's lever, where attend length is the per-token
        cost)."""
        if bucket in self._prefill_fns:
            return self._prefill_fns[bucket]
        ps, num_pages = self.page_size, self.num_pages
        reach = self.max_pages * ps
        from ..ops import kv_cache as kvc

        def shard_body(params, pool: PagedKVCache, tokens, length, base,
                       table):
            t = jnp.arange(bucket, dtype=jnp.int32)
            real = t < length
            positions = jnp.where(real, base + t, PAD_POS)[None, :]
            # Padded tails get logical row = reach -> beyond the table
            # -> flat row num_pages * ps -> the scatter DROPS them (the
            # same drop discipline the contiguous offset prefill uses).
            logical = jnp.where(real, base + t, reach)[None, :]
            flat = kvc.table_rows(table, logical, ps, num_pages)
            logits, pool = self._paged_forward(
                params, pool, tokens, table, positions=positions,
                flat_rows=flat,
            )
            return logits[0], pool

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._pspecs, self._pcspecs, P_(), P_(), P_(), P_()),
            out_specs=(P_(), self._pcspecs),
            check_vma=False,
        )

        def run(params, pool, tokens, length, base, table, request_id):
            logits, pool = shard(params, pool, tokens, length, base, table)
            last = lax.dynamic_index_in_dim(
                logits, length - 1, axis=0, keepdims=False
            )
            nxt = self._sample(last, request_id, base + length)
            return nxt, logits, pool

        fn = self._ledgered(
            "prefill", bucket,
            jax.jit(_named(run, f"run_prefill_b{bucket}"),
                    donate_argnums=donation_for(self.mesh, 1)),
        )
        self._prefill_fns[bucket] = fn
        self._note_compile("prefill", bucket)
        return fn

    def _decode_paged(self, pages: int):
        """Paged decode at page-count bucket ``pages`` — THE paged perf
        lever: attention gathers ``pages * page_size`` rows per slot
        instead of ``capacity``, so per-token cost tracks what the batch
        actually holds. One compiled program per bucket (powers of two
        capped at the table width), same sampling contract as the
        contiguous ``_decode``. Inactive slots' writes map out of
        bounds and DROP — a mid-prefill or free slot touches nothing."""
        if pages in self._decode_paged_fns:
            return self._decode_paged_fns[pages]
        ps, num_pages = self.page_size, self.num_pages
        from ..ops import kv_cache as kvc

        def shard_body(params, pool, last_tokens, lengths, active, table):
            positions = jnp.where(active, lengths, PAD_POS)[:, None]
            logical = jnp.where(active, lengths, pages * ps)[:, None]
            flat = kvc.table_rows(table, logical, ps, num_pages)
            logits, pool = self._paged_forward(
                params, pool, last_tokens[:, None], table,
                positions=positions, flat_rows=flat,
            )
            return logits[:, 0], pool

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._pspecs, self._pcspecs, P_(), P_(), P_(), P_()),
            out_specs=(P_(), self._pcspecs),
            check_vma=False,
        )

        def run(params, pool, last_tokens, lengths, request_ids, active,
                table):
            logits, pool = shard(params, pool, last_tokens, lengths,
                                 active, table)
            nxt = jax.vmap(self._sample)(logits, request_ids, lengths + 1)
            return nxt, logits, pool

        fn = self._ledgered(
            "decode", pages,
            jax.jit(_named(run, f"run_decode_p{pages}"),
                    donate_argnums=donation_for(self.mesh, 1)),
        )
        self._decode_paged_fns[pages] = fn
        self._note_compile("decode", pages)
        return fn

    def _copy_page(self):
        """Compiled CoW tail-page copy (``serve.cache.copy_page``): the
        ONLY copy program on the paged prefix path. Slot/page ids and
        the row count are traced — one program total."""
        if self._copy_page_fn is not None:
            return self._copy_page_fn

        def shard_body(pool, src_page, dst_page, n):
            return copy_page(pool, src_page=src_page, dst_page=dst_page,
                             n=n)

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._pcspecs, P_(), P_(), P_()),
            out_specs=self._pcspecs,
            check_vma=False,
        )
        self._copy_page_fn = self._ledgered(
            "prefix_copy", 0,
            jax.jit(shard, donate_argnums=donation_for(self.mesh, 0)),
        )
        self._note_compile("prefix_copy", 0)
        return self._copy_page_fn

    def _write_page(self):
        """Compiled whole-page write (``serve.cache.write_page``): the
        receive half of cross-replica preemption (``serve.controller``).
        Page id traced — one program total; the K/V rows arrive with the
        pool's own head-dim tp sharding."""
        if self._write_page_fn is not None:
            return self._write_page_fn

        if self.quantized:
            def shard_body(pool, dst_page, k_rows, v_rows, pos_rows,
                           ks_rows, vs_rows):
                return write_page(pool, dst_page=dst_page, k_rows=k_rows,
                                  v_rows=v_rows, pos_rows=pos_rows,
                                  k_scale_rows=ks_rows,
                                  v_scale_rows=vs_rows)

            in_specs = (self._pcspecs, jax.sharding.PartitionSpec(),
                        self._pcspecs.k, self._pcspecs.v,
                        self._pcspecs.pos, self._pcspecs.k_scale,
                        self._pcspecs.v_scale)
        else:
            def shard_body(pool, dst_page, k_rows, v_rows, pos_rows):
                return write_page(pool, dst_page=dst_page, k_rows=k_rows,
                                  v_rows=v_rows, pos_rows=pos_rows)

            in_specs = (self._pcspecs, jax.sharding.PartitionSpec(),
                        self._pcspecs.k, self._pcspecs.v,
                        self._pcspecs.pos)

        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=self._pcspecs,
            check_vma=False,
        )
        self._write_page_fn = self._ledgered(
            "page_write", 0,
            jax.jit(shard, donate_argnums=donation_for(self.mesh, 0)),
        )
        self._note_compile("page_write", 0)
        return self._write_page_fn

    def dump_slot_pages(self, slot: int):
        """Serialize ``slot``'s resident pages host-side — the send half
        of cross-replica preemption: ``(k, v, pos)`` numpy arrays of
        shape ``[L, n, page, H, D]`` / ``[n, page]`` where ``n`` is the
        slot's mapped page count, in BLOCK-TABLE order (the order the
        gathered attend view reconstructs), assembled across tp shards
        by ``device_get``. A host round-trip moves bits, not values —
        the destination's attend view is bit-identical by
        construction.

        Int8 pools return ``k``/``v`` as ``(payload, scale)`` PAIRS
        (int8 rows + their fp32 per-head scales) — the host layers
        (``scheduler.preempt``'s ``PreemptedRequest``, the controller,
        the disagg coordinator) store and forward them opaquely, so the
        hand-off moves the compressed bytes and ``load_slot_pages`` on
        the destination reassembles the exact source rows."""
        if not self.paged:
            raise RuntimeError(
                "dump_slot_pages needs the paged KV layout (page_size > "
                "0) — the contiguous ring has no slot-independent pages "
                "to hand off"
            )
        n = int(self.table_len[slot])
        pages = jnp.asarray(self.tables[slot, :n], jnp.int32)

        def take(leaf, axis):
            return np.asarray(jax.device_get(jnp.take(leaf, pages,
                                                      axis=axis)))

        k = take(self.cache.k, 1)
        v = take(self.cache.v, 1)
        pos = take(self.cache.pos, 0)
        if self.quantized:
            return ((k, take(self.cache.k_scale, 1)),
                    (v, take(self.cache.v_scale, 1)), pos)
        return k, v, pos

    def load_slot_pages(self, slot: int, k, v, pos) -> list[int]:
        """Make serialized page contents resident in ``slot``: map one
        FRESH page per source page (consuming the slot's admission
        reservation, exactly like prefill growth) and overwrite it whole
        with the serialized rows. The freshly mapped page was fully
        ``PAD_POS`` (free-list invariant) and the written ``pos`` rows
        carry the source's own ``PAD_POS`` tail, so nothing stale is
        ever attendable. Returns the mapped page ids (table order).
        Int8 pools receive ``k``/``v`` as the ``(payload, scale)``
        pairs their ``dump_slot_pages`` produced — payloads and scales
        land together, page by page."""
        if not self.paged:
            raise RuntimeError(
                "load_slot_pages needs the paged KV layout (page_size > 0)"
            )
        ks = vs = None
        if self.quantized:
            if not (isinstance(k, tuple) and isinstance(v, tuple)):
                raise ValueError(
                    "int8 pool: load_slot_pages needs the (payload, "
                    "scale) pairs dump_slot_pages produced — a bare "
                    "payload came from a full-precision dump and would "
                    "dequantize to garbage"
                )
            k, ks = k
            v, vs = v
        elif isinstance(k, tuple) or isinstance(v, tuple):
            raise ValueError(
                "full-precision pool: load_slot_pages got (payload, "
                "scale) pairs — the dump came from an int8 engine; "
                "hand-offs need matching kv_dtype on both replicas"
            )
        n = int(k.shape[1])
        fn = self._write_page()
        mapped = []
        for i in range(n):
            page = self._map_page(slot)
            kk = multihost.put(self.mesh, self._pcspecs.k,
                               np.ascontiguousarray(k[:, i:i + 1]))
            vv = multihost.put(self.mesh, self._pcspecs.v,
                               np.ascontiguousarray(v[:, i:i + 1]))
            pp = multihost.put(self.mesh, self._pcspecs.pos,
                               np.ascontiguousarray(pos[i:i + 1]))
            if self.quantized:
                kks = multihost.put(self.mesh, self._pcspecs.k_scale,
                                    np.ascontiguousarray(ks[:, i:i + 1]))
                vvs = multihost.put(self.mesh, self._pcspecs.v_scale,
                                    np.ascontiguousarray(vs[:, i:i + 1]))
                self.cache = fn(self.cache, jnp.int32(page), kk, vv, pp,
                                kks, vvs)
            else:
                self.cache = fn(self.cache, jnp.int32(page), kk, vv, pp)
            mapped.append(page)
        return mapped

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
        if not self.paged:
            raise RuntimeError(
                "alias_slot_pages needs the paged KV layout "
                "(page_size > 0) — contiguous slots have no pages to "
                "alias"
            )
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

    def decode_page_bucket(self, pages: int) -> int:
        """The page-count bucket ladder: smallest power of two >=
        ``pages``, capped at the table width — a handful of compiled
        decode programs cover every residency."""
        b = 1
        while b < pages:
            b *= 2
        return min(b, self.max_pages)

    # -- prefix-cache device half ------------------------------------------

    def _copy_fn(self, *, into_cache: bool):
        """Compiled slot-to-slot prefix copy between the serving cache
        and the prefix pool (``serve.cache.copy_slot_prefix`` under
        ``shard_map``): ``into_cache=True`` is the HIT path (pool row
        gather into a decode slot, cache donated), ``False`` the
        REGISTRATION path (freshly prefilled prompt rows into a pool
        slot, pool donated). One program each — slot indices and the
        row count are traced."""
        cached = self._copy_in if into_cache else self._copy_out
        if cached is not None:
            return cached

        def shard_body(cache, pool, src_slot, dst_slot, n):
            if into_cache:
                return copy_slot_prefix(cache, pool, src_slot=src_slot,
                                        dst_slot=dst_slot, n=n)
            return copy_slot_prefix(pool, cache, src_slot=src_slot,
                                    dst_slot=dst_slot, n=n)

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._cspecs, self._cspecs, P_(), P_(), P_()),
            out_specs=self._cspecs,
            check_vma=False,
        )
        fn = self._ledgered(
            "prefix_copy", int(into_cache),
            jax.jit(
                shard,
                donate_argnums=donation_for(self.mesh,
                                            0 if into_cache else 1),
            ),
        )
        if into_cache:
            self._copy_in = fn
        else:
            self._copy_out = fn
        self._note_compile("prefix_copy", int(into_cache))
        return fn

    def prefix_fetch(self, entry_id: int, n: int, slot: int) -> int:
        """HIT: make the first ``n`` rows of entry ``entry_id`` resident
        in decode ``slot`` and pin the entry (refcount) until the caller
        releases it — LRU pressure can never free a prefix a live
        request was admitted from. Returns the number of K/V rows
        DEVICE-COPIED for the hit.

        Contiguous mode: one donated gather program copies all ``n``
        rows pool -> slot (returns ``n``). Paged mode: the entry's full
        pages map straight into the slot's block table (incref — ZERO
        copies); only when ``n`` is not page-aligned does the one
        PARTIAL boundary page copy-on-write into a freshly mapped page
        (returns ``n % page_size`` — the ``page_copies`` counter and
        the scheduler's trace events assert exactly this bound)."""
        e = self.prefix.entry(entry_id)
        if self.paged:
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
            copied = 0
            if tail:
                # The entry always covers the boundary page: its token
                # coverage is a page multiple >= any match depth n.
                dst = self._map_page(slot)
                self.cache = self._copy_page()(
                    self.cache, jnp.int32(int(e.pages[shared])),
                    jnp.int32(dst), jnp.int32(tail),
                )
                self.page_copies += 1
                copied = tail
            self.prefix.touch(entry_id)
            self.prefix.acquire(entry_id)
            return copied
        self.cache = self._copy_fn(into_cache=True)(
            self.cache, self.pool,
            jnp.int32(e.slot), jnp.int32(slot), jnp.int32(n),
        )
        self.prefix.touch(entry_id)
        self.prefix.acquire(entry_id)
        return n

    def prefix_release(self, entry_id: int) -> None:
        self.prefix.release(entry_id)

    def prefix_store(self, prompt, slot: int) -> bool:
        """REGISTRATION: index ``prompt`` and make its freshly prefilled
        rows ``0..p-1`` resident for future hits. Must run before the
        slot's first decode write (the scheduler does — row ``p`` is
        still stale here). False = registration skipped (index full of
        pinned entries, or — paged — the prompt spans no full page).

        Contiguous mode snapshots the rows into a claimed pool slot (one
        donated copy program). Paged mode DONATES instead of
        snapshotting: the entry takes a reference on each of the slot's
        FULL prompt pages (the partial last page stays slot-private —
        decode is about to write into it), so registration moves zero
        K/V bytes and the pages are shared from that moment on. The
        slot's own reference keeps every donated page live until it
        finishes, so an eviction racing this insert can never free
        them."""
        prompt = np.asarray(prompt, np.int32)
        if self.paged:
            full = int(prompt.shape[0]) // self.page_size
            if full < 1:
                return False
            pages = [int(p) for p in self.tables[slot, :full]]
            got = self.prefix.insert(
                prompt[: full * self.page_size], pages=pages
            )
            if got is None:
                return False
            for page in pages:
                self.pages.incref(page)
            return True
        got = self.prefix.insert(prompt)
        if got is None:
            return False
        _, pool_slot = got
        self.pool = self._copy_fn(into_cache=False)(
            self.cache, self.pool,
            jnp.int32(slot), jnp.int32(pool_slot),
            jnp.int32(int(prompt.shape[0])),
        )
        return True

    # -- host API ----------------------------------------------------------

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

    def prefill(self, prompt, *, slot: int, request_id: int, base: int = 0,
                _bucket: int | None = None):
        """Prefill one prompt BLOCK into ``slot``: writes rows
        ``base..base+t-1`` (positions likewise), samples sequence
        element ``base + t``. ``base == 0`` with the whole prompt is
        classic admission; ``base > 0`` resumes after a prefix-cache
        copy or an earlier chunk — the sampled token is only meaningful
        when the block ends at the prompt's last token. Returns
        ``(next_token int, logits np [t, vocab])`` — the logits of
        every position in the block, for parity pinning and scoring.
        ``_bucket`` forces a larger bucket than ``t`` needs — the
        warmup ladder's compile trigger, so compiling a big bucket
        costs one real row (and, paged, one page) instead of a full
        bucket of writes."""
        prompt = np.asarray(prompt, np.int32)
        t = int(prompt.shape[0])
        if base < 0 or base + t > self.config.capacity:
            raise ValueError(
                f"prefill block [base={base}, base+{t}) outside cache "
                f"capacity {self.config.capacity}"
            )
        bucket = self.prefill_bucket(t) if _bucket is None else _bucket
        assert bucket >= t, (bucket, t)
        with span("engine.upload", kind="prefill"):
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :t] = prompt
            if self.paged:
                self._ensure_rows(slot, base + t)
                fn = self._prefill_paged_fn(bucket)
                where = jnp.asarray(self.tables[slot:slot + 1])
            else:
                fn = self._prefill_fn(bucket)
                where = jnp.int32(slot)
            args = (jnp.asarray(tokens), jnp.int32(t), jnp.int32(base),
                    where, jnp.int32(request_id))
        nxt, logits = self._call("prefill", fn, args)
        return int(nxt), logits[:t]

    def decode(self, last_tokens, lengths, request_ids, active, *,
               _pages: int | None = None):
        """One batched decode step over all slots. Host arrays in,
        ``(next_tokens np [S], logits np [S, vocab])`` out; the fetch is
        the step's true barrier (latency timing hangs off it).

        Paged mode first maps any page a growing slot is about to cross
        into (consuming its admission reservation — this can never find
        the pool empty), then runs the program whose PAGE-COUNT bucket
        covers the widest ACTIVE table: attend cost tracks residency.
        A mid-prefill slot's wider table truncates harmlessly — it is
        inactive, so its writes drop and its outputs are discarded.
        ``_pages`` forces a bucket (warmup's compile trigger, called
        with every slot inactive so no state moves)."""
        with span("engine.upload", kind="decode"):
            lengths_np = np.asarray(lengths, np.int32)
            active_np = np.asarray(active, bool)
            tables = ()
            if self.paged:
                if _pages is None:
                    widest = 1
                    for s in np.nonzero(active_np)[0]:
                        self._ensure_rows(int(s), int(lengths_np[s]) + 1)
                        widest = max(widest, int(self.table_len[s]))
                    pb = self.decode_page_bucket(widest)
                else:
                    pb = _pages
                self.last_attend_width = pb * self.page_size
                fn = self._decode_paged(pb)
                tables = (self.tables[:, :pb],)
            else:
                fn = self._decode()
            args = tuple(jnp.asarray(a) for a in (
                np.asarray(last_tokens, np.int32), lengths_np,
                np.asarray(request_ids, np.int32), active_np, *tables))
        return self._call("decode", fn, args)

    def _call(self, kind: str, fn, args):
        """Run one compiled program on the placed params and the cache
        and bring back what it returns, ``(next np, logits np)``, as
        three phases: the dispatch returns before the device ends, the
        fetch of ``next`` blocks until it has, the fetch of the logits
        is then a plain transfer. The ``engine.*`` spans have no tracer:
        they reach the profiler alone and read no clock."""
        with span("engine.dispatch", kind=kind):
            nxt, logits, self.cache = fn(self.params, self.cache, *args)
        with span("engine.wait", kind=kind):
            nxt = np.asarray(nxt)
        with span("engine.fetch_logits", kind=kind):
            logits = np.asarray(logits)
        return nxt, logits
