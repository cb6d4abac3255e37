"""The inference engine: a jitted ``(prefill, decode)`` pair over the
serving mesh.

This is the device half of the serving subsystem (batching policy is
``serve.scheduler``; the engine's own host half — config check, block
tables, reservations, prefix paging — is ``serve.host.EngineHost``,
which the twin ``serve.sim`` inherits too). Two compiled programs cover
a request's whole life:

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
    cache_specs,
    copy_page,
    copy_slot_prefix,
    host_cache,
    host_paged_cache,
    paged_cache_specs,
    write_page,
)
from .host import EngineHost
from .speculate import SPECULATE_METHODS


def _named(fn, name: str):
    """``fn`` under ``name``: XLA calls a jitted function's program
    ``jit_<name>``, which is all a device trace shows of it. Prefill
    programs are ``run_prefill_b<bucket>`` (paged) / ``_c<bucket>``
    (contiguous), decode ``run_decode_p<pages>`` / ``run_decode_c``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _arg(*shape, dtype=jnp.int32):
    """One argument of a program's packed layout (:func:`_takes_packed`)."""
    return jax.ShapeDtypeStruct(shape, dtype)


def pack_args(values) -> np.ndarray:
    """One call's arguments as the one int32 vector its program takes:
    each value that is not ``None`` flattened, in the order given (an
    int one element, a bool array 0 / 1)."""
    return np.concatenate([np.asarray(v, np.int32).reshape(-1)
                           for v in values if v is not None])


def unpack_args(packed, layout) -> tuple:
    """The arguments :func:`pack_args` put in ``packed``, cut out by
    static slices: each entry of ``layout`` gives one's shape and dtype
    (a bool is ``!= 0``), ``None`` an argument that was not sent."""
    n = sum(a.size for a in layout if a is not None)
    if packed.shape != (n,) or packed.dtype != jnp.int32:
        raise ValueError(f"this program takes {n} packed int32 values, got "
                         f"{packed.dtype}{list(packed.shape)}")
    args, at = [], 0
    for a in layout:
        if a is None:
            args.append(None)
            continue
        v = packed[at:at + a.size].reshape(a.shape)
        args.append(v != 0 if a.dtype == jnp.bool_ else v)
        at += a.size
    return tuple(args)


def _takes_packed(run, layout):
    """``run(params, cache, *args)`` as a program takes it: ``(params,
    cache, packed)``, one host-to-device transfer a call. ``layout``,
    fixed when the program is built, is ``args``' shapes and dtypes."""

    def take(params, cache, packed):
        return run(params, cache, *unpack_args(packed, layout))

    return take


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
    honors the jit's donation, so the dispatch semantics are the jit's
    own. ``lower``
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
    layout (the module docstring's "Paged KV pool"): one shared pool of
    ``num_pages`` pages of ``page_size`` rows. ``capacity`` still bounds
    one slot's reach (``capacity // page_size`` block-table entries).
    ``num_pages = 0`` defaults to ``slots * capacity / page_size`` — the
    slot-major memory envelope, no pooling savings but drop-in.
    ``page_size = 0`` (the default) is the contiguous cache, retained as
    the bit-exactness oracle (tests/test_serve_paged.py).

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
    # None = fp32; "bfloat16" = MXU path, the dense engine's weights held
    # in it (cast once when placed: InferenceEngine._place)
    compute_dtype: str | None = None
    prefix_slots: int = 0  # prefix-cache pool width; 0 = off
    prefill_chunk: int = 0  # chunked-prefill block; 0 = whole-prompt
    prefill_budget: int = 0  # prefill tokens per scheduler tick; 0 = all
    page_size: int = 0  # paged KV layout: rows per page; 0 = contiguous
    num_pages: int = 0  # paged pool size; 0 = slots * capacity / page_size
    kv_dtype: str | None = None  # "int8" = quantized paged pool; None = full
    # Speculative decoding (ISSUE 15, serve.speculate): k > 0 drafts up
    # to k tokens per active slot per tick and verifies them through
    # FREE SLOTS of the one batched decode call (zero new programs —
    # the draft lanes alias the speculating slot's pages); what that
    # requires is in REFUSED. method: "ngram" (prompt + generated
    # lookup) or "prompt" (prompt-only lookup). k = 0 is the byte-identical
    # pre-speculation tick (HLO-pinned in tests/test_serve_speculate).
    speculate_k: int = 0
    speculate_method: str = "ngram"

    def dtype(self):
        return None if self.compute_dtype is None else jnp.dtype(self.compute_dtype)

    def check(self, also=()) -> tuple[int, int, int]:
        """Raise for the first rule of ``also`` (one engine's own) or
        :data:`REFUSED` that this config breaks; then the page geometry
        ``(page_size, max_pages, num_pages)``: ``max_pages`` the block
        table's width, all zero for the contiguous cache. Called by
        every engine's constructor (``EngineHost._configure``)."""
        for asked, message in (*also, *REFUSED):
            if asked(self):
                raise ValueError(message.format(
                    c=self, methods=", ".join(SPECULATE_METHODS)))
        ps = self.page_size
        if not ps:
            return 0, 0, 0
        max_pages = self.capacity // ps
        return ps, max_pages, self.num_pages or self.slots * max_pages


def _pow2(n: int) -> bool:
    return n > 0 and not n & (n - 1)


# What no engine serves, whatever its family or device, so that the real
# engines and the twin fail alike, at construction, never mid-run: every
# rule that reads a ServeConfig alone, as ``(asked(config), message)``
# pairs in the order they are refused, the message formatted with
# ``c=config``.
REFUSED = (
    (lambda c: c.slots < 1 or c.capacity < 2,
     "need slots >= 1 and capacity >= 2, got {c.slots} / {c.capacity}"),
    (lambda c: not 0 <= c.top_k <= c.spec.vocab,
     "top_k must be in [0, vocab={c.spec.vocab}], got {c.top_k}"),
    (lambda c: c.prefix_slots < 0,
     "prefix_slots must be >= 0, got {c.prefix_slots}"),
    # Power-of-two >= 8: a chunk is ITS OWN prefill bucket (plus the
    # smaller buckets any final partial chunk already uses), keeping the
    # compiled-program count logarithmic.
    (lambda c: c.prefill_chunk
     and not (c.prefill_chunk >= 8 and _pow2(c.prefill_chunk)),
     "prefill_chunk must be 0 or a power of two >= 8, got "
     "{c.prefill_chunk}"),
    (lambda c: c.prefill_budget and not c.prefill_chunk,
     "prefill_budget requires prefill_chunk (the budget meters chunk "
     "interleaving; whole-prompt prefill ignores it silently otherwise)"),
    (lambda c: c.prefill_budget and c.prefill_budget < c.prefill_chunk,
     "prefill_budget ({c.prefill_budget}) below prefill_chunk "
     "({c.prefill_chunk}) could never start a chunk"),
    (lambda c: c.page_size and not _pow2(c.page_size),
     "page_size must be 0 (contiguous) or a power of two, got "
     "{c.page_size} (pages tile the capacity and the row->page split is "
     "a shift/mask)"),
    (lambda c: c.num_pages and not c.page_size,
     "num_pages ({c.num_pages}) requires page_size > 0 (the contiguous "
     "layout has no page pool)"),
    (lambda c: c.num_pages < 0, "num_pages must be >= 0, got {c.num_pages}"),
    # int8 storage is a property of the PAGE pool — the contiguous ring
    # is the bit-exactness oracle and stays full-precision.
    (lambda c: c.kv_dtype not in (None, "int8"),
     "kv_dtype must be None or 'int8', got {c.kv_dtype!r}"),
    (lambda c: c.kv_dtype == "int8" and not c.page_size,
     "kv_dtype='int8' needs the paged KV layout (page_size > 0): "
     "quantized storage lives in the shared page pool; the contiguous "
     "ring is the full-precision oracle"),
    # Every speculation requirement is structural — a violated one could
    # only surface as silently-never-speculating or a mid-run lane
    # failure.
    (lambda c: c.speculate_k < 0,
     "speculate_k must be >= 0, got {c.speculate_k}"),
    (lambda c: c.speculate_method not in SPECULATE_METHODS,
     "speculate_method must be one of {methods}, got "
     "{c.speculate_method!r}"),
    (lambda c: c.speculate_k > 0 and not c.page_size,
     "speculate_k={c.speculate_k} needs the paged KV layout (page_size > "
     "0): draft lanes verify through block-table ALIASES of the "
     "speculating slot's pages, and contiguous slot rings have no pages "
     "to alias"),
    (lambda c: c.speculate_k > 0 and c.temperature > 0.0,
     "speculate_k={c.speculate_k} needs temperature=0 (greedy): greedy-"
     "accept is what keeps speculative output bit-identical to plain "
     "decode; sampled acceptance is a different algorithm"),
    (lambda c: c.speculate_k > 0 and c.slots < 2,
     "speculate_k={c.speculate_k} needs slots >= 2: drafts verify through "
     "FREE slots of the batched decode, and a 1-slot batch has no lane to "
     "ride"),
    (lambda c: c.page_size and c.capacity % c.page_size,
     "capacity ({c.capacity}) must be a multiple of page_size "
     "({c.page_size}) — the block table holds whole pages"),
    (lambda c: 0 < c.num_pages < c.slots,
     "num_pages ({c.num_pages}) below slots ({c.slots}) — every admitted "
     "slot needs at least one page; the pool could never fill the batch"),
)


def _load_host_params(path, spec, init=transformer.init_lm_params):
    """Params-only host tree from any trainer checkpoint: the template
    is shapes-only (``jax.eval_shape`` — no arrays are initialized just
    to be overwritten)."""
    from ..utils.checkpoint import load_params

    template = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), spec))
    host, _, _ = load_params(path, template)
    return host


class InferenceEngine(EngineHost):
    """Owns the placed params, the cache state, and the compiled
    program pair. ``params`` is a host pytree (e.g. a fresh init or a
    ``utils.checkpoint.load_params`` result); ``None`` seeds a random
    init — the smoke/demo path. ``placed_params`` instead SHARES an
    already-placed device tree from another engine on an identical
    mesh (the multi-replica router's one-checkpoint contract,
    ISSUE 8) — no re-placement, no transient duplicate copy; safe
    because no compiled program donates the params argument.

    The host half of :class:`~ddl_tpu.serve.engine_iface.ServeEngine`
    is the base class's; here are the pools, the programs, its hooks."""

    # What a family's engine brings (``serve.hybrid_engine`` is the
    # second; ``serve.engine_cls(spec)`` picks the class): its spec
    # type, a fresh host tree, its layout (``_layout``) and how a host
    # tree becomes the resident one (``_place``), beside its pools
    # (``reset``) and programs.
    spec_type = LMSpec
    _init_params = staticmethod(transformer.init_lm_params)
    refuses = (
        (lambda c: c.tensor_parallel < 1,
         "tensor_parallel must be >= 1, got {c.tensor_parallel}"),
        (lambda c: c.spec.num_heads % c.tensor_parallel,
         "tensor_parallel needs num_heads ({c.spec.num_heads}) divisible "
         "by tp ({c.tensor_parallel})"),
        (lambda c: c.spec.d_ff % c.tensor_parallel,
         "tensor_parallel needs d_ff ({c.spec.d_ff}) divisible by tp "
         "({c.tensor_parallel})"),
    )

    def __init__(self, config: ServeConfig, params=None, *,
                 placed_params=None):
        spec = config.spec
        if not isinstance(spec, self.spec_type):
            raise ValueError(
                f"{type(self).__name__} serves a {self.spec_type.__name__}, "
                f"got a {type(spec).__name__}: serve.engine_cls(spec) is "
                "the class of the spec's family"
            )
        self._configure(config, params, placed_params)
        self.quantized = config.kv_dtype == "int8"
        tp = config.tensor_parallel
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
        self._prefill_fns: dict[int, object] = {}
        # The all-rows prefill form (a caller asked for logits): built
        # on the first such call of a bucket, never by warmup.
        self._prefill_rows_fns: dict[int, object] = {}
        self._decode_fn = None
        self._decode_paged_fns: dict[int, object] = {}
        self._copy_in = None  # pool slot -> cache slot (prefix hit)
        self._copy_out = None  # cache slot -> pool slot (registration)
        self._copy_page_fn = None  # paged CoW: partial tail page
        self._write_page_fn = None  # paged: cross-replica page hand-off
        self._reset_pages_fn = None  # paged: PAD_POS freed pages' pos
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

    def _program(self, kind: str, key: int, name: str, run, layout):
        """One prefill or decode program: ``run(params, cache, *args)``
        named ``name``, taking ``args`` packed (``layout``:
        :func:`_takes_packed`), the cache donated; jitted, ledgered and
        noted as built."""
        fn = self._ledgered(kind, key, jax.jit(
            _named(_takes_packed(run, layout), name),
            donate_argnums=donation_for(self.mesh, 1)))
        self._note_compile(kind, key)
        return fn

    # -- state -------------------------------------------------------------

    def reset(self) -> None:
        """Fresh (empty) cache — every slot free, nothing attendable —
        beside fresh host state (``EngineHost._reset_host``): the pools
        and what indexes them reset TOGETHER."""
        self._reset_host()
        dtype = np.dtype(self.config.compute_dtype or np.float32)
        if self.paged:
            self.cache = multihost.put_tree(
                self.mesh, self._pcspecs,
                host_paged_cache(self.config.spec, self.num_pages,
                                 self.page_size, dtype,
                                 kv_dtype=self.config.kv_dtype),
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

    # -- the host half's device hooks (serve.host) --------------------------

    def _pages_freed(self, pages) -> None:
        """Freed pages get their device ``pos`` rows reset to
        ``PAD_POS`` (one batched scatter — the free-list invariant that
        lets a freshly mapped page join the gathered attend view with
        nothing attendable)."""
        while pages:
            batch, pages = pages[: self.max_pages], pages[self.max_pages:]
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

    def _copy_tail_page(self, src_page: int, dst_page: int, n: int) -> None:
        self.cache = self._copy_page()(
            self.cache, jnp.int32(src_page), jnp.int32(dst_page),
            jnp.int32(n),
        )

    def load_params(self, path) -> None:
        """Params-only checkpoint load (``utils.checkpoint.load_params``):
        accepts a trainer checkpoint from ANY topology — optimizer/step
        state is ignored if present and not required to exist."""
        self.params = self._place(_load_host_params(
            path, self.config.spec, self._init_params))

    def _place(self, params):
        """A host tree onto the mesh, as the programs take it: in the
        compute dtype where one is set, cast once on the device after
        each leaf is placed (a tp shard keeps its PartitionSpec), so no
        program casts a weight again. No copy in the handed-over dtype
        is kept: nothing in serving writes weights. With
        ``compute_dtype=None`` the tree keeps its own dtypes."""
        placed = multihost.put_tree(self.mesh, self._pspecs, params)
        dtype = self.config.dtype()
        if dtype is None:
            return placed
        return jax.tree.map(lambda a: a.astype(dtype), placed)

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

        def body(params, cache: KVCache, tokens, start, positions, rows=None,
                 last_row=None):
            logits, k, v, pos = transformer.apply_lm_cached(
                params, tokens, cache.k, cache.v, cache.pos, cfg.spec,
                start=start, positions=positions, rows=rows,
                compute_dtype=cfg.dtype(), row_reduce=self._row_reduce,
                last_row=last_row,
            )
            return logits, KVCache(k=k, v=v, pos=pos)

        return body

    def _prefill_fn(self, bucket: int, all_rows: bool = False):
        """Compiled prefill for prompt blocks padded to ``bucket``
        tokens: ``(params, cache, packed) -> (next_token, logits [1,
        vocab], cache)``, ``packed`` holding ``tokens [1, bucket],
        length, base, slot, request_id`` (:func:`_takes_packed`):
        the head is applied to the last real row alone, the one the
        token is sampled from. ``all_rows`` is the other form, for a
        caller that asked for logits: ``logits [bucket, vocab]`` of
        every row, a program of its own (``run_prefill_rows_c<bucket>``).
        ``base`` is the slot's position offset — 0 for a whole
        prompt, the copied-prefix length after a prefix-cache hit, the
        running offset for chunk 2+ of a chunked prefill. One program
        per bucket and form covers every ``(length, base)``."""
        fns = self._prefill_rows_fns if all_rows else self._prefill_fns
        if bucket in fns:
            return fns[bucket]
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
                             jnp.zeros((1,), jnp.int32), positions, rows,
                             None if all_rows else length - 1)
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
        fns[bucket] = self._prefill_program(shard, bucket, all_rows, "c",
                                            _arg())
        return fns[bucket]

    def _prefill_program(self, shard, bucket: int, all_rows: bool,
                         layout: str, where):
        """Either layout's prefill program around its ``shard``: sample
        from the last real row's logits, name, jit and ledger it.
        ``where`` is the slot (contiguous) or its table (paged); the
        argument ``where``, its shape."""

        def run(params, cache, tokens, length, base, where, request_id):
            logits, cache = shard(params, cache, tokens, length, base, where)
            last = logits[0]
            if all_rows:
                last = lax.dynamic_index_in_dim(
                    logits, length - 1, axis=0, keepdims=False
                )
            # The sampled token is sequence element `base + length` of
            # this request — the token_index the PRNG key folds in (only
            # the block ending at the prompt's last token uses it; the
            # scheduler discards mid-prompt samples).
            nxt = self._sample(last, request_id, base + length)
            return nxt, logits, cache

        kind, form = ("prefill_rows", "rows_") if all_rows else ("prefill", "")
        return self._program(kind, bucket,
                             f"run_prefill_{form}{layout}{bucket}", run,
                             self._prefill_layout(bucket, where))

    def _decode(self):
        """Compiled decode step: one token for every slot at once.
        ``(params, cache, packed) -> (next_tokens [S], logits [S,
        vocab], cache)``, ``packed`` holding ``last_tokens [S], lengths
        [S], request_ids [S], active [S]``."""
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

        self._decode_fn = self._program("decode", 0, "run_decode_c", run,
                                        self._decode_layout())
        return self._decode_fn

    # -- paged compiled programs -------------------------------------------

    def _paged_forward(self, params, pool: PagedKVCache, tokens, table,
                       *, positions, flat_rows, last_row=None):
        """The one ``apply_lm_paged`` call both paged programs trace:
        routes the pool's scale planes in (and the updated planes back
        out) when the pool is int8 — a STATIC branch on
        ``self.quantized``, so the full-precision programs are
        byte-identical to pre-int8 builds."""
        cfg = self.config
        platform = self.mesh.devices.flat[0].platform
        if self.quantized:
            logits, k, v, pos, ks, vs = transformer.apply_lm_paged(
                params, tokens, pool.k, pool.v, pool.pos, table,
                cfg.spec, positions=positions, flat_rows=flat_rows,
                compute_dtype=cfg.dtype(), row_reduce=self._row_reduce,
                pool_k_scale=pool.k_scale, pool_v_scale=pool.v_scale,
                platform=platform, last_row=last_row,
            )
            return logits, PagedKVCache(k=k, v=v, pos=pos,
                                        k_scale=ks, v_scale=vs)
        logits, k, v, pos = transformer.apply_lm_paged(
            params, tokens, pool.k, pool.v, pool.pos, table, cfg.spec,
            positions=positions, flat_rows=flat_rows,
            compute_dtype=cfg.dtype(), row_reduce=self._row_reduce,
            platform=platform, last_row=last_row,
        )
        return logits, PagedKVCache(k=k, v=v, pos=pos)

    def _prefill_paged_fn(self, bucket: int, all_rows: bool = False):
        """Paged prefill for prompt blocks padded to ``bucket`` tokens:
        ``(params, pool, packed) -> (next_token, logits [1, vocab],
        pool)``, or ``logits [bucket, vocab]`` in the ``all_rows`` form
        (``run_prefill_rows_b<bucket>``); ``packed`` holds ``tokens [1,
        bucket], length, base, table [1, max_pages], request_id``. Same
        sampling/offset/form contract
        as the contiguous ``_prefill_fn`` — writes route through the
        slot's block table instead of a slot slice, padded tails map
        OUT OF BOUNDS (dropped), and the table is passed at its FULL
        width (prefill is matmul-bound; the page-count bucket ladder is
        the DECODE program's lever, where attend length is the per-token
        cost)."""
        fns = self._prefill_rows_fns if all_rows else self._prefill_fns
        if bucket in fns:
            return fns[bucket]
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
                flat_rows=flat, last_row=None if all_rows else length - 1,
            )
            return logits[0], pool

        P_ = jax.sharding.PartitionSpec
        shard = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(self._pspecs, self._pcspecs, P_(), P_(), P_(), P_()),
            out_specs=(P_(), self._pcspecs),
            check_vma=False,
        )
        fns[bucket] = self._prefill_program(shard, bucket, all_rows, "b",
                                            _arg(1, self.max_pages))
        return fns[bucket]

    def _decode_paged(self, pages: int):
        """Paged decode at page-count bucket ``pages`` — THE paged perf
        lever: attention gathers ``pages * page_size`` rows per slot
        instead of ``capacity``, so per-token cost tracks what the batch
        actually holds. One compiled program per bucket (powers of two
        capped at the table width), same sampling contract as the
        contiguous ``_decode``, ``packed`` holding its four vectors and
        ``table [S, pages]``. Inactive slots' writes map out of bounds
        and DROP — a mid-prefill or free slot touches nothing."""
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

        fn = self._program("decode", pages, f"run_decode_p{pages}", run,
                           self._decode_layout(_arg(self.config.slots,
                                                    pages)))
        self._decode_paged_fns[pages] = fn
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
        self._need_pages("dump_slot_pages")
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
        self._need_pages("load_slot_pages")
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
        """``EngineHost.prefix_fetch`` over pages; on the contiguous
        cache one donated gather program copies all ``n`` rows pool ->
        slot (returns ``n``)."""
        if self.paged:
            return super().prefix_fetch(entry_id, n, slot)
        self.cache = self._copy_fn(into_cache=True)(
            self.cache, self.pool,
            jnp.int32(self.prefix.entry(entry_id).slot), jnp.int32(slot),
            jnp.int32(n),
        )
        self._pin(entry_id)
        return n

    def prefix_store(self, prompt, slot: int) -> bool:
        """``EngineHost.prefix_store`` over pages; on the contiguous
        cache the rows are snapshot into a claimed pool slot (one
        donated copy program)."""
        if self.paged:
            return super().prefix_store(prompt, slot)
        prompt = np.asarray(prompt, np.int32)
        got = self.prefix.insert(prompt)
        if got is None:
            return False
        self.pool = self._copy_fn(into_cache=False)(
            self.cache, self.pool, jnp.int32(slot), jnp.int32(got[1]),
            jnp.int32(int(prompt.shape[0])),
        )
        return True

    # -- host API ----------------------------------------------------------

    def prefill(self, prompt, *, slot: int, request_id: int, base: int = 0,
                _bucket: int | None = None, want_logits: bool = False):
        """Prefill one prompt BLOCK into ``slot``: writes rows
        ``base..base+t-1`` (positions likewise), samples sequence
        element ``base + t``. ``base == 0`` with the whole prompt is
        classic admission; ``base > 0`` resumes after a prefix-cache
        copy or an earlier chunk — the sampled token is only meaningful
        when the block ends at the prompt's last token. Returns
        ``(next_token int, None)``: the program applies the head to the
        block's last real row alone and nothing but the sampled id
        leaves the device. With ``want_logits`` the second member is
        ``logits np [t, vocab]`` — every position in the block, for
        parity pinning and scoring — from the all-rows program, built
        on the first such call of a bucket.
        ``_bucket`` forces a larger bucket than ``t`` needs — the
        warmup ladder's compile trigger, so compiling a big bucket
        costs one real row (and, paged, one page) instead of a full
        bucket of writes."""
        prompt, t, bucket = self._prefill_block(prompt, base, _bucket)
        with span("engine.upload", kind="prefill"):
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :t] = prompt
            where = self._prefill_where(slot, base, t)
            fn = (self._prefill_paged_fn if self.paged
                  else self._prefill_fn)(bucket, want_logits)
            packed = self._h2d("prefill",
                               (tokens, t, base, *where, request_id))
        out, logits = self._call("prefill", fn, packed, want_logits)
        self._counted("prefill", out[1:])
        return int(out[0]), logits[:t] if want_logits else None

    def decode(self, last_tokens, lengths, request_ids, active, *,
               _pages: int | None = None, want_logits: bool = False):
        """One batched decode step over all slots. Host arrays in,
        ``(next_tokens np [S], None)`` out, or ``(next_tokens, logits np
        [S, vocab])`` with ``want_logits`` (the same program: its logits
        stay on the device unless asked for); the fetch of the tokens is
        the step's true barrier (latency timing hangs off it).

        Paged mode runs the program of ``EngineHost._decode_bucket``:
        attend cost tracks residency. ``_pages`` forces a bucket
        (warmup's compile trigger, called with every slot inactive)."""
        with span("engine.upload", kind="decode"):
            lengths_np = np.asarray(lengths, np.int32)
            active_np = np.asarray(active, bool)
            fn, where = self._decode_where(lengths_np, active_np, _pages)
            packed = self._h2d("decode", (last_tokens, lengths_np,
                                          request_ids, active_np, *where))
        out, logits = self._call("decode", fn, packed, want_logits)
        slots = self.config.slots
        self._counted("decode", out[slots:], lengths_np, active_np)
        return out[:slots], logits

    def _prefill_where(self, slot: int, base: int, t: int) -> tuple:
        """Make room for rows ``base..base+t-1`` of ``slot``; the
        prefill program's arguments that say where they go, as host
        values (:meth:`_h2d` packs and uploads them)."""
        if not self.paged:
            return (slot,)
        self._ensure_rows(slot, base + t)
        return (self.tables[slot:slot + 1],)

    def _decode_where(self, lengths, active, _pages) -> tuple:
        """Make room for each active slot's next row; the decode program
        of the bucket that covers them and its table arguments, as host
        values."""
        if not self.paged:
            return self._decode(), ()
        pb = self._decode_bucket(lengths, active, _pages)
        return self._decode_paged(pb), (self.tables[:, :pb],)

    def _prefill_layout(self, bucket: int, *where) -> tuple:
        """What a prefill program's ``run`` takes after the params and
        the cache, in the order :meth:`prefill` packs it: ``tokens [1,
        bucket], length, base, *where, request_id``."""
        return (_arg(1, bucket), _arg(), _arg(), *where, _arg())

    def _decode_layout(self, *where) -> tuple:
        """A decode program's likewise: ``last_tokens, lengths,
        request_ids, active`` (``[slots]`` each), ``*where``."""
        s = self.config.slots
        return (_arg(s), _arg(s), _arg(s), _arg(s, dtype=jnp.bool_), *where)

    @staticmethod
    def _h2d(kind: str, values: tuple):
        """One call's arguments in ONE host->device transfer, inside
        ``engine.h2d`` (``arrays``: the transfers made, 1; ``bytes``:
        the packed buffer's): :func:`pack_args` of ``values``, ``None``
        (an argument the program does not take) left out."""
        nbytes = 4 * sum(np.size(v) for v in values if v is not None)
        with span("engine.h2d", kind=kind, arrays=1, bytes=nbytes):
            return jnp.asarray(pack_args(values))

    def _counted(self, kind: str, counts, lengths=None, active=None) -> None:
        """What a program returned behind its sampled ids (``counts``);
        the dense family's programs count nothing."""

    def _call(self, kind: str, fn, packed, want_logits: bool):
        """Run one compiled program on the placed params, the cache
        and ``packed`` (already on the device: :meth:`_h2d`, inside
        ``engine.upload``) and bring back what the host reads of it,
        ``(out np [n], logits np or None)``: ``out`` is the small array
        that starts with the sampled ids, the logits come only if the
        caller asked in this call. Three phases: the dispatch returns
        before the device ends, the fetch of ``out`` blocks until it
        has, the fetch of the logits, if any, is then a plain transfer;
        its span is entered on every call. The ``engine.*`` spans have
        no tracer: they reach the profiler alone and read no clock."""
        with span("engine.dispatch", kind=kind):
            out, logits, self.cache = fn(self.params, self.cache, packed)
        with span("engine.wait", kind=kind):
            out = np.asarray(out).reshape(-1)
        with span("engine.fetch_logits", kind=kind):
            logits = np.asarray(logits) if want_logits else None
        return out, logits
