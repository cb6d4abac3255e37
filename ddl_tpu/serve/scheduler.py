"""Continuous batching: the host-side driver over the engine's
``(prefill, decode)`` pair.

Static batching (run a batch to completion, then admit the next) leaves
slots idle as soon as the first sequence finishes; continuous batching
— the Orca/vLLM scheduling discipline — admits and evicts at TOKEN
granularity: every tick, finished sequences free their slots, waiting
requests prefill into them, and ONE fixed-shape decode program advances
every active slot together. The device never sees the churn: admission
is a prefill into a slot slice, eviction is host bookkeeping (the
position-masked cache makes stale rows invisible, serve/cache.py).

Two admission optimizations ride on the engine's offset prefill
(ISSUE 4 tentpole), both OFF by default and bit-transparent when on:

- **Prefix-cache reuse** (``ServeConfig.prefix_slots``): each admission
  asks the engine's ``PrefixIndex`` for the longest cached prefix of
  the prompt; a hit of >= ``MIN_PREFIX_HIT`` tokens becomes one device
  row-copy plus a TAIL-only prefill at ``base = hit`` (at least the
  last prompt token always re-prefills — sampling needs its logits).
  Completed prompt prefills register back into the pool (refcounted
  LRU, serve/prefix.py); a request admitted from an entry pins it until
  the request finishes.
- **Chunked prefill** (``ServeConfig.prefill_chunk``): prompts stream
  in fixed chunks interleaved with decode ticks under a per-tick token
  budget (``prefill_budget``), so one long prompt no longer stalls
  every active decoder for its whole prefill — the inter-token-latency
  tail (``ServeStats.itl``) is the metric it bounds. A slot being
  chunk-prefilled is occupied but not yet decoding.

**Paged admission** (ISSUE 7): when the engine runs the paged KV pool
(``ServeConfig.page_size``), admission becomes "enough free pages" —
the scheduler reserves ``ceil((prompt + max_new) / page_size)`` pages
(minus the full pages a prefix hit shares) before claiming a slot, so
capacity pools ACROSS requests instead of reserving a worst-case ring
per slot. When the queue head cannot fit, it WAITS (strict FIFO — no
head-of-line bypass, so runs stay deterministic) after first asking the
engine to reclaim pages from zero-ref prefix entries. Completion and
deadline eviction release pages identically (``engine.release_slot``).
Per-tick gauges ``serve_kv_pages_free`` / ``serve_kv_pages_shared`` and
the ``kv_pages_held`` attribute on ``complete`` events surface the pool
story through the PR 5 registry/trace surfaces.

The scheduler is deliberately pure Python — policy lives here (arrival
order, slot choice, stop conditions, prefix/chunk policy), device work
lives in the jitted engine. Determinism contract: sampling keys depend
only on ``(seed, request_id, token_index)``, slot computation is
row-independent, and copied prefix rows are bit-identical to the rows a
fresh prefill would write — so a request's output tokens are identical
whatever mix of strangers shares the batch, whenever it arrives, and
whether the prefix cache or chunking is on or off (pinned by
tests/test_serve.py against cache-off and isolated runs).

Robustness (ISSUE 6): per-request **TTFT/total deadlines** (wall
seconds from eligibility; per-request fields override scheduler
defaults) — expiry EVICTS the request, freeing its slot and releasing
any pinned prefix refs, and returns
``Completion(status="deadline_exceeded")`` with the partial tokens; a
queued request past its deadline is cancelled without ever admitting.
**Admission shedding** (``shed_threshold``): a request whose first
eligible tick finds outstanding work (occupied slots + waiting
eligibles) at the threshold is refused with ``status="shed"`` — under
overload the newest arrivals degrade instead of every admitted
request's ITL. Both validated at construction (non-positive deadlines
and thresholds below the slot count are config errors, not silent
no-ops); both count into the registry (``serve_deadline_exceeded_total``,
``serve_shed_total``) and trace as events. Eviction is host bookkeeping
exactly like completion (masked cache rows are invisible), so
co-resident requests' tokens are bit-identical with or without a
neighbour being evicted (pinned in tests/test_resilience.py).

Metrics: prefill tok/s, decode tok/s/slot, per-decode-step latency
p50/p95/p99, TTFT (wall clock from arrival-eligibility to first
token), ITL (gap between consecutive decode completions while slots
stayed active — the stall chunking bounds), and prefix-cache
hit-rate / prefill-tokens-saved.

Telemetry (ISSUE 5): constructed with an ``obs.Tracer``, the scheduler
emits the full request lifecycle as events/spans —
``submit -> eligible -> admit -> prefix_copy -> prefill_chunk ->
first_token -> decode_tick -> complete`` — each stamped with the SAME
``perf_counter`` values the ``ServeStats`` math uses, so
:func:`derive_request_slo` recovers TTFT/ITL from the trace EXACTLY
equal to ``ServeStats.ttft``/``.itl`` (pinned at tp=1 and tp=2 in
tests/test_obs.py). With an ``obs.MetricRegistry``, the scheduler
keeps counters (prefill/decode tokens, prefix ledger, completions),
per-tick gauges (queue depth, active/occupied slots, prefix-pool
entries) and latency histograms (ttft / itl / decode step / prefill)
— observed from the same brackets as the ``StepTimer``s, so the two
surfaces can never disagree. ``warmup`` suppresses both (compile
traffic must not pollute a run's telemetry). Both default off, and
every clock read they add is gated on the tracer/registry being
present — a bare ``Scheduler(engine)`` runs the exact
pre-observability tick loop.

Time attribution (ISSUE 11): with a registry, every tick's wall time
decomposes into the ``obs.goodput`` serve phases — prefill / decode /
prefix_copy (the existing StepTimer brackets, attributed as they
close), shed (the shed/deadline sweep), and the tick residual as host
(device work happened) or idle (it did not) — published live as
``time_in_seconds{phase=}`` / ``goodput_fraction`` gauges with the
pinned identity that phases sum to observed tick time. An optional
``anomaly_detector`` (``obs.anomaly``) is scored once per tick over
step_time / itl / mfu / queue_depth / active_slots / occupied_slots /
pages_free; the host-state signals are deterministic functions of the
tick clock, which is what pins the stall-injection scenario's anomaly
to identical ticks across runs (tests/test_goodput.py).

Disaggregation & speculation (ISSUE 15): ``role="prefill"`` makes this
scheduler a prompt-ingestion specialist — the decode phase is skipped
wholesale and first-token slots are HELD for the fleet coordinator's
page hand-off (``serve.disagg``; the preempt/adopt machinery below is
the transfer). ``ServeConfig.speculate_k > 0`` replaces the plain
decode phase with :meth:`_speculate_decode`: still exactly one batched
decode call per tick, but free slots become draft LANES verifying
n-gram-lookup proposals (``serve.speculate``) — greedy-accept keeps the
output BIT-IDENTICAL to plain decode while emitting up to k+1 tokens
per target step. Both default off; the off paths are byte-identical to
the pre-ISSUE-15 tick.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from ..obs import comms as _comms
from ..obs import cost as _cost
from ..obs.goodput import GoodputTracker
from ..obs.memory import MemorySampler, record_compile
from ..obs.trace import NULL_TRACER, span
from ..utils.metrics import StepStats, StepTimer
from .engine import InferenceEngine
from .speculate import greedy_accept, propose_draft

# A prefix hit shorter than this prefills normally: every BOS-led prompt
# trivially shares its first token with every cached entry, and a
# one-row copy is pure overhead dressed up as a hit.
MIN_PREFIX_HIT = 2


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``arrival`` is the earliest scheduler
    step at which it may be admitted — tests and benchmarks stagger
    arrivals with it; a live frontend would enqueue with ``arrival=0``.

    Deadlines (ISSUE 6): ``ttft_deadline_s`` bounds eligibility → first
    token, ``deadline_s`` eligibility → completion (both wall seconds;
    None inherits the scheduler's defaults). Expiry EVICTS the request
    — slot freed, pinned prefix refs released — and returns a
    ``Completion(status="deadline_exceeded")`` with whatever tokens
    were generated, instead of holding a slot forever.

    ``traffic_class`` (ISSUE 8) names the request's SLO class for the
    multi-replica router (``serve.router``) — the scheduler itself
    ignores it; per-class accounting lives one layer up.

    ``shed_exempt`` (ISSUE 13): the admission-shed check skips this
    request. Set by the fleet controller when re-queuing a request that
    was ALREADY ADMITTED before its replica crashed — its admission
    decision was made once and must not be re-made against the
    post-crash backlog (a crash must never convert served work into a
    refusal)."""

    id: int
    prompt: np.ndarray  # int32 [p], p >= 1
    max_new_tokens: int
    arrival: int = 0
    ttft_deadline_s: float | None = None
    deadline_s: float | None = None
    traffic_class: str = "default"
    shed_exempt: bool = False


@dataclasses.dataclass
class Completion:
    """``status`` is the structured outcome: ``"ok"`` (ran to its stop
    condition), ``"deadline_exceeded"`` (evicted at a TTFT/total
    deadline — ``tokens`` holds the partial output), ``"shed"``
    (refused at admission under overload; never occupied a slot), or
    ``"requeued"`` (ISSUE 13: a TRANSIENT placeholder the fleet
    controller writes for a crash-orphaned request — overwritten
    exactly once by the final completion when the re-run lands; it
    survives only if the run is torn down before the fleet heals)."""

    id: int
    prompt_len: int
    tokens: list[int]  # generated ids (includes the eos token if hit)
    admitted_step: int  # -1: never admitted (shed / expired in queue)
    finished_step: int
    status: str = "ok"


@dataclasses.dataclass
class ServeStats:
    """Aggregate throughput/latency for one :meth:`Scheduler.run`."""

    prefill_tokens: int
    prefill_s: float
    decode_tokens: int
    decode_steps: int
    decode_s: float
    slots: int
    latency: StepStats  # per-decode-step = per-token percentiles
    # Serving SLO additions (ISSUE 4): time-to-first-token per request
    # (queueing + prefix copy + prefill), inter-token latency (decode-
    # completion gaps INCLUDING interleaved prefill work — the stall
    # chunked prefill bounds), and the prefix-cache ledger.
    ttft: StepStats = dataclasses.field(
        default_factory=lambda: StepStats.from_times([])
    )
    itl: StepStats = dataclasses.field(
        default_factory=lambda: StepStats.from_times([])
    )
    prefix_lookups: int = 0
    prefix_hits: int = 0
    prefill_tokens_saved: int = 0

    @property
    def prefill_tokens_per_s(self) -> float:
        return self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0

    @property
    def decode_tokens_per_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def decode_tokens_per_s_per_slot(self) -> float:
        return self.decode_tokens_per_s / self.slots

    @property
    def prefix_hit_rate(self) -> float:
        return (self.prefix_hits / self.prefix_lookups
                if self.prefix_lookups else 0.0)


@dataclasses.dataclass
class PreemptedRequest:
    """A mid-decode request lifted out of one scheduler for resumption
    on another (ISSUE 13, ``serve.controller``): the request, its
    generated-so-far stream, the decode cursor, and its KV pages
    serialized host-side (``engine.dump_slot_pages`` — bit-exact rows,
    block-table order). ``eligible_wall`` carries the ORIGINAL
    eligibility stamp so deadlines keep their meaning across the move,
    and ``admitted_at`` the original admission step so the eventual
    ``Completion`` reports the request's true admission."""

    request: Request
    generated: list[int]
    last_token: int
    lengths: int
    admitted_at: int
    eligible_wall: float
    k: np.ndarray  # [L, n_pages, page, H, D]; int8 pools: (payload, scale)
    v: np.ndarray  # [L, n_pages, page, H, D]; int8 pools: (payload, scale)
    pos: np.ndarray  # [n_pages, page]


@dataclasses.dataclass(frozen=True)
class Pressure:
    """Non-destructive scheduler load probe (ISSUE 8 satellite): the
    numbers a router needs to place traffic, read through one method
    instead of reaching into run-loop state. Field-for-field equal to
    the registry gauges the tick loop publishes (pinned in
    tests/test_serve.py): ``occupied_slots`` ≡ ``serve_occupied_slots``,
    ``active_slots`` ≡ ``serve_active_slots``, ``pages_free`` ≡
    ``serve_kv_pages_free`` (0 on the contiguous layout),
    ``prefix_entries`` ≡ ``serve_prefix_pool_entries``.
    ``waiting_eligible`` counts arrivals due at the NEXT tick's clock —
    the routing-relevant reading — which equals the just-published
    ``serve_queue_depth`` gauge (stamped with the finished tick's
    clock) whenever every pending arrival is already due; with
    still-future arrivals the probe runs one step ahead of the gauge.
    ``pages_available`` additionally
    subtracts admission reservations — the true headroom the paged
    admission path gates on (no gauge twin; reservations are promised
    capacity, not free capacity). Between runs every queue/slot field
    reads 0."""

    occupied_slots: int
    active_slots: int
    waiting_eligible: int  # submitted, arrival reached, not yet admitted
    pending_total: int  # submitted and not yet admitted, future arrivals too
    pages_free: int  # paged pool only; 0 contiguous
    pages_available: int  # pages_free minus admission reservations
    prefix_entries: int

    @property
    def outstanding(self) -> int:
        """Occupied slots + waiting eligibles — the same quantity the
        shed threshold compares against (ISSUE 6)."""
        return self.occupied_slots + self.waiting_eligible


class _RunState:
    """Everything one :meth:`Scheduler.run` used to keep in locals,
    lifted into an object so a run can be driven EXTERNALLY tick by
    tick (``begin``/``submit``/``tick``/``collect`` — the router's
    replica-stepping loop, ISSUE 8) and probed mid-flight
    (:meth:`Scheduler.pressure`)."""

    def __init__(self, slots: int):
        self.pending: collections.deque = collections.deque()
        self.occupant: list[Request | None] = [None] * slots
        self.active = np.zeros(slots, bool)  # decoding (prefill complete)
        self.lengths = np.zeros(slots, np.int32)  # tokens resident
        self.last_tokens = np.zeros(slots, np.int32)  # sampled, unappended
        self.req_ids = np.zeros(slots, np.int32)
        self.generated: list[list[int]] = [[] for _ in range(slots)]
        self.admitted_at = np.zeros(slots, np.int64)
        self.prefilled = np.zeros(slots, np.int64)  # prompt tokens in cache
        self.store_after = [False] * slots  # register prompt when done
        self.held_entry = [-1] * slots  # pinned pool entry behind admission
        self.done: dict[int, Completion] = {}
        self.prefill_timer = StepTimer()
        self.decode_timer = StepTimer()
        self.eligible_wall: dict[int, float] = {}
        self.ttfts: list[float] = []
        self.itls: list[float] = []
        self.lookups = self.hits = self.saved = 0
        self.last_decode_done: float | None = None
        self.step = 0
        self.deadlines_on = False
        self.seen_ids: set[int] = set()


class Scheduler:
    """Continuous-batching driver. One instance per engine; ``run`` is
    synchronous and returns when every request has completed. For
    externally-timed driving (the multi-replica router, ISSUE 8) the
    same run decomposes into ``begin`` / ``submit`` / ``tick`` /
    ``collect`` with ``pressure()`` as the non-destructive load probe —
    ``run`` is literally that sequence, so the two forms cannot drift.
    ``allow_window=True`` admits requests whose ``prompt +
    max_new_tokens`` exceeds the cache capacity — the ring wraps and
    attention degrades to an EXACT sliding window over the last
    ``capacity`` positions mid-generation, which is a semantics change
    the caller must opt into, never stumble into (the default rejects
    at submit, naming the request)."""

    def __init__(self, engine: InferenceEngine, *, eos_id: int | None = None,
                 allow_window: bool = False, tracer=None, registry=None,
                 metrics_writer=None, ttft_deadline_s: float | None = None,
                 deadline_s: float | None = None,
                 shed_threshold: int | None = None, injector=None,
                 slo_monitor=None, peak_flops: float | None = None,
                 anomaly_detector=None, role: str = "mixed"):
        self.engine = engine
        self.eos_id = eos_id
        # Disaggregated serving (ISSUE 15, serve.disagg): a "prefill"-
        # role scheduler runs prompts to their first token and then
        # HOLDS the slot — the decode phase is skipped wholesale, and
        # the fleet coordinator lifts the finished prefix out with the
        # ordinary preempt/adopt page hand-off. "decode" replicas
        # behave exactly like "mixed" (the split is enforced by the
        # router's placement, not here); "mixed" is the default and the
        # byte-identical pre-disaggregation tick.
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"role must be 'mixed', 'prefill' or 'decode', got "
                f"{role!r}"
            )
        if role != "mixed" and not engine.handoff:
            raise ValueError(
                f"role={role!r} needs an engine whose pages can be handed "
                "off; this family's page groups cannot yet"
            )
        if role == "prefill" and not engine.paged:
            raise ValueError(
                "role='prefill' needs the paged KV layout (page_size > "
                "0): the prefill->decode hand-off moves KV pages, and "
                "contiguous slot rings have none"
            )
        self.role = role
        if allow_window and engine.paged:
            raise ValueError(
                "allow_window is a ring-buffer (contiguous) semantics — "
                "the paged layout never wraps (a wrap would stomp shared "
                "prefix pages); size capacity/num_pages for the full "
                "request instead"
            )
        self.allow_window = allow_window
        # Resilience config (ISSUE 6), validated at CONSTRUCTION in
        # _validate's submit-time style — a bad value is a loud error
        # naming the offender, never a silently-never-firing deadline
        # or a shed threshold that refuses servable traffic.
        for name, v in (("ttft_deadline_s", ttft_deadline_s),
                        ("deadline_s", deadline_s)):
            if v is not None and v <= 0:
                raise ValueError(
                    f"{name} must be > 0 seconds, got {v} (a non-positive "
                    "deadline would expire every request at its first "
                    "tick)"
                )
        if shed_threshold is not None and shed_threshold < engine.config.slots:
            raise ValueError(
                f"shed_threshold ({shed_threshold}) is below the engine's "
                f"concurrent capacity (slots={engine.config.slots}) — it "
                "would shed traffic the batch could serve; use a value "
                ">= slots"
            )
        self.ttft_deadline_s = ttft_deadline_s
        self.deadline_s = deadline_s
        self.shed_threshold = shed_threshold
        # Deterministic fault injector (resilience.faults): `stalls(id)`
        # defers that request's prefill forever — the hung-upstream
        # model the deadline eviction path is pinned against.
        self.injector = injector
        # Telemetry (module docstring): request-lifecycle tracer,
        # metric registry and (rate-limited) JSONL snapshot writer, all
        # optional and all suppressed during warmup. NULL_TRACER is
        # falsy, so `if self.tracer:` guards even the extra clock reads
        # off the disabled path.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.registry = registry
        self.metrics_writer = metrics_writer
        # Live SLO control plane (ISSUE 10): an obs.slo.SloMonitor
        # advanced once per tick (its windows are tick windows — the
        # deterministic clock), a MemorySampler for device watermark
        # gauges (self-latching off on backends without memory_stats),
        # a peak-FLOPs resolution for the serve_mfu gauge, and the
        # engine compile hook feeding xla_compiles_total. All absent
        # when telemetry is off — the off path is byte-identical.
        self.slo_monitor = slo_monitor
        if slo_monitor is not None and slo_monitor.registry is not registry:
            raise ValueError(
                "slo_monitor was built on a different registry than this "
                "scheduler's — it would read metrics the scheduler never "
                "writes (burn 0.0 forever). Build it on the registry "
                "passed as registry="
            )
        # Anomaly detection (ISSUE 11): an obs.anomaly.AnomalyDetector
        # scored once per tick with the tick's signal vocabulary —
        # step_time / itl / mfu (wall-clock) and queue_depth /
        # active_slots / occupied_slots / pages_free (deterministic
        # host state, the signals the pinned scenarios use).
        self.anomaly = anomaly_detector
        if anomaly_detector is not None \
                and anomaly_detector.registry is not registry:
            raise ValueError(
                "anomaly_detector was built on a different registry than "
                "this scheduler's — its anomaly_* metrics would land "
                "where nothing reads them. Build it on the registry "
                "passed as registry="
            )
        self._peak_flops = peak_flops
        self._peak: float | None = None
        self._mem = None
        # Goodput attribution (ISSUE 11): every tick's wall time lands
        # in exactly one phase (obs.goodput — prefill/decode/
        # prefix_copy/shed/idle/host), published live next to
        # serve_mfu. A ctor feature like the memory sampler: no
        # registry -> no tracker, no extra clock reads.
        self._goodput = None
        if registry is not None:
            self._mem = MemorySampler(registry, engine.mesh.devices.flat)
            self._goodput = GoodputTracker(registry, "serve")

            def _on_build(kind, key, _sched=self):
                # Registry captured directly (compile activity during
                # warmup IS signal); the tracer read dynamically so
                # warmup's suppressed tracer stays suppressed.
                record_compile(registry, _sched.tracer, kind, key=key)

            engine.compile_hook = _on_build

            def _on_ledger(kind, key, compiled):
                # Static collective ledger (ISSUE 20): every distinct
                # compiled program publishes its collective-op bytes
                # once, labelled to join the xla_compiles_total kinds.
                # Registry captured like _on_build — warmup compiles
                # are the same programs the run will dispatch.
                _comms.publish_program_ledger(
                    registry, _comms.program_text(compiled),
                    program=f"{kind}[{key}]", mesh=engine.mesh,
                )

            engine.ledger_hook = _on_ledger
        # Externally-driven run state (ISSUE 8): armed by begin(),
        # advanced by tick(), finalized by collect()/release(). run()
        # is sugar over the same four primitives.
        self._st: _RunState | None = None

    @property
    def goodput(self):
        """The live :class:`obs.goodput.GoodputTracker` (None without a
        registry) — the attribution read surface (ISSUE 11)."""
        return self._goodput

    def attach_registry(self, registry) -> None:
        """Swap the live metric registry mid-lifetime (the bench's
        per-repetition isolation, ISSUE 11): rebuilds the ctor-time
        consumers that capture it — the goodput tracker and memory
        sampler — so a post-hoc attach gets the same gauges a
        ctor-time registry does. The engine compile hook keeps its
        ctor registry (compile activity belongs to the build that
        compiled, not to whichever rep runs next). A bound SLO
        monitor/anomaly detector pins the registry: swapping under
        them would strand their metrics (or unbind `depth` for the
        anomaly feed) — the same invariant the ctor enforces, so the
        swap is rejected loudly here too."""
        for name, consumer in (("slo_monitor", self.slo_monitor),
                               ("anomaly_detector", self.anomaly)):
            if consumer is not None and consumer.registry is not registry:
                raise ValueError(
                    f"attach_registry would strand the bound {name} on "
                    "its old registry (the ctor-enforced same-registry "
                    "invariant); rebuild it on the new registry first "
                    "or detach it"
                )
        self.registry = registry
        self._mem = self._goodput = None
        if registry is not None:
            self._mem = MemorySampler(registry,
                                      self.engine.mesh.devices.flat)
            self._goodput = GoodputTracker(registry, "serve")

    def warmup(self, requests) -> None:
        """Compile the decode program and every prefill bucket / prefix
        copy program ``requests`` will need, OUTSIDE any timed run, then
        reset the engine to a fresh cache AND an empty prefix pool —
        reported latency/throughput must measure serving, not jit (the
        BASELINE.md methodology; shared by the serve CLI and
        serve_bench so the two can never measure differently). Clones
        carry fresh negative ids and generate at most 2 tokens (enough
        to compile decode whenever the real run will decode at all) —
        which changes slot-free timing vs the real run, so prefix-hit
        TAIL lengths (and hence buckets) can differ between the two:
        the whole power-of-two bucket ladder up to the largest prompt
        is compiled explicitly below, plus both prefix copy programs,
        so no admission path the real run takes can jit inside a timed
        bracket."""
        if not requests:
            return
        eng = self.engine
        # Compile traffic must not pollute the run's telemetry: the
        # clone run emits no lifecycle events and moves no counters
        # (the derived-TTFT pin would otherwise see the warmup's
        # negative-id requests). Deadlines, shedding and fault
        # injection are likewise suppressed — a warmup clone evicted or
        # shed would skip compiling the programs the real run needs.
        saved = (self.tracer, self.registry, self.metrics_writer,
                 self.ttft_deadline_s, self.deadline_s,
                 self.shed_threshold, self.injector, self.slo_monitor,
                 self._mem, self.anomaly, self._goodput, self.role)
        self.tracer, self.registry, self.metrics_writer = \
            NULL_TRACER, None, None
        self.ttft_deadline_s = self.deadline_s = None
        self.shed_threshold = self.injector = None
        # A prefill-role scheduler HOLDS first-token slots for the
        # fleet coordinator — warmup has no coordinator, so the clone
        # run warms as "mixed" (which also compiles the decode ladder
        # this replica needs if the controller ever re-roles traffic
        # through it).
        self.role = "mixed"
        # The SLO monitor, memory sampler, anomaly detector and goodput
        # tracker are per-TICK consumers: warmup's clone ticks must not
        # advance burn-rate/baseline windows, sample watermarks or
        # attribute compile-warm time mid-compile (the engine compile
        # hook stays live — compile activity during warmup IS its
        # signal).
        self.slo_monitor = self._mem = None
        self.anomaly = self._goodput = None
        try:
            self.run([
                dataclasses.replace(
                    r, id=-1 - i,
                    max_new_tokens=min(2, r.max_new_tokens),
                    ttft_deadline_s=None, deadline_s=None,
                )
                for i, r in enumerate(requests)
            ])
            # Suppression covers the COMPILE LADDERS below too, not
            # just the clone run: the engine compile hook reads
            # self.tracer dynamically, so a warmup build traces nothing
            # (the "warmup emits no records" pin) while its
            # xla_compiles_total count — registry captured directly in
            # the hook — still lands.
            if eng.paged:
                # The clone run may leave prefix entries holding pages;
                # the compile ladders below need a clean pool (a tight
                # pool could otherwise exhaust mid-warmup). Warmup
                # discards all engine state at the end regardless.
                eng.reset()
            # A chunked run never prefills a block past its chunk: the
            # buckets above it would be compiled (and run, at the top
            # bucket's temporaries) for nothing.
            longest = max(int(np.asarray(r.prompt).shape[0])
                          for r in requests)
            max_bucket = eng.prefill_bucket(
                min(longest, eng.config.prefill_chunk or longest))
            b = 8
            while True:
                # min() also covers a capacity-capped (non-power-of-two)
                # top bucket the doubling ladder would step over. The
                # 1-token prompt at a FORCED bucket compiles the program
                # with one real row — so the paged ladder costs one
                # page, not a worst-case table's worth.
                bucket = min(b, max_bucket)
                eng.prefill(np.zeros(1, np.int32), slot=0, request_id=-1,
                            base=0, _bucket=bucket)
                if bucket == max_bucket:
                    break
                b *= 2
            if eng.paged:
                eng.release_slot(0)
                # Decode is keyed by PAGE-COUNT bucket: compile the
                # ladder up to the widest residency the real run can
                # reach (the truncated clones never grow past ~2
                # generated tokens, so the big buckets would otherwise
                # jit inside a timed bracket). All-inactive batches
                # compile without moving state: every write maps out of
                # bounds and drops.
                top = eng.decode_page_bucket(eng.pages_needed(max(
                    min(int(np.asarray(r.prompt).shape[0])
                        + r.max_new_tokens, eng.config.capacity)
                    for r in requests
                )))
                S = eng.config.slots
                zeros = np.zeros(S, np.int32)
                pb = 1
                while True:
                    pbi = min(pb, eng.max_pages)
                    eng.decode(zeros, zeros, zeros, np.zeros(S, bool),
                               _pages=pbi)
                    if pbi >= top:
                        break
                    pb *= 2
            if eng.prefix is not None:
                if eng.paged:
                    # The paged hit path moves no K/V rows EXCEPT the
                    # CoW partial-tail-page copy — seed two full pages,
                    # register (zero-copy donation), and take one
                    # page-UNALIGNED hit so that one program compiles
                    # here, not mid-run. Tiny pools (< 3 pages of
                    # headroom) skip — such a run compiles it lazily on
                    # its first unaligned hit.
                    ps = eng.page_size
                    if eng.max_pages >= 2 and eng.num_pages >= 3:
                        eng.prefill(np.zeros(2 * ps, np.int32), slot=0,
                                    request_id=-1, base=0)
                        if eng.prefix_store(np.zeros(2 * ps, np.int32),
                                            0):
                            entry, _ = eng.prefix.match(
                                np.zeros(2 * ps, np.int32)
                            )
                            eng.release_slot(0)
                            eng.prefix_fetch(entry, ps + 1, 0)
                            eng.prefix_release(entry)
                # One store + fetch compiles both contiguous copy
                # programs even when the truncated clone run happened
                # to produce no hit.
                elif eng.prefix_store(np.zeros(2, np.int32), 0):
                    entry, _ = eng.prefix.match(np.zeros(2, np.int32))
                    eng.prefix_fetch(entry, 2, 0)
                    eng.prefix_release(entry)
            self.engine.reset()
        finally:
            (self.tracer, self.registry, self.metrics_writer,
             self.ttft_deadline_s, self.deadline_s,
             self.shed_threshold, self.injector, self.slo_monitor,
             self._mem, self.anomaly, self._goodput, self.role) = saved

    def _validate(self, r: Request) -> None:
        """Reject a malformed request at SUBMIT time — ``run`` validates
        every request before admitting ANY, so one oversized prompt in a
        batch of valid ones fails the whole call with a per-request
        diagnosis and no partial state (no slot prefilled, no cache rows
        written) instead of letting ``engine.prefill_bucket`` raise
        mid-run after other slots were already admitted."""
        cap = self.engine.config.capacity
        p = int(np.asarray(r.prompt).shape[0])
        if p < 1:
            raise ValueError(f"request {r.id}: empty prompt")
        if r.max_new_tokens < 1:
            raise ValueError(f"request {r.id}: max_new_tokens must be >= 1")
        if p > cap:
            # Named separately from the combined budget below: the fix
            # is a bigger --capacity (or a shorter prompt), not a
            # smaller max_new_tokens.
            raise ValueError(
                f"request {r.id}: prompt length {p} exceeds cache "
                f"capacity {cap}"
            )
        if p + r.max_new_tokens > cap and not self.allow_window:
            # Without the check the ring would silently wrap into
            # sliding-window attention mid-generation — a semantics
            # change, not an error, so it is opt-in only. On the paged
            # layout this bound is the block-TABLE REACH (max_pages
            # pages) and there is no window escape hatch (pages never
            # wrap) — same loud submit-time rejection, naming the fix.
            if self.engine.paged:
                raise ValueError(
                    f"request {r.id}: prompt ({p}) + max_new_tokens "
                    f"({r.max_new_tokens}) exceeds the block-table reach "
                    f"({self.engine.max_pages} pages x "
                    f"{self.engine.page_size} rows = {cap}); raise "
                    "--capacity (table width) or shorten the request"
                )
            raise ValueError(
                f"request {r.id}: prompt ({p}) + max_new_tokens "
                f"({r.max_new_tokens}) exceeds cache capacity {cap} "
                f"(pass allow_window=True to accept sliding-window "
                f"attention once the ring wraps)"
            )
        if self.engine.paged:
            need = self.engine.pages_needed(p + r.max_new_tokens)
            if need > self.engine.num_pages:
                # The whole-pool bound: even an otherwise-empty engine
                # could never hold this request's worst case.
                raise ValueError(
                    f"request {r.id}: prompt ({p}) + max_new_tokens "
                    f"({r.max_new_tokens}) needs {need} KV pages but the "
                    f"pool holds num_pages={self.engine.num_pages}; "
                    "raise --num-pages or shorten the request"
                )
        for name, v in (("ttft_deadline_s", r.ttft_deadline_s),
                        ("deadline_s", r.deadline_s)):
            if v is not None and v <= 0:
                raise ValueError(
                    f"request {r.id}: {name} must be > 0 seconds, got {v}"
                )
        if self.injector is not None and self.injector.stalls(r.id) \
                and self._deadline_for(r) == (None, None):
            raise ValueError(
                f"request {r.id}: stall fault injected but no TTFT/total "
                "deadline applies — the run would never terminate; set a "
                "per-request or scheduler-default deadline"
            )

    def _deadline_for(self, r: Request) -> tuple[float | None, float | None]:
        """Effective ``(ttft, total)`` wall-second deadlines for a
        request: per-request values win, scheduler defaults fill in."""
        ttft = r.ttft_deadline_s if r.ttft_deadline_s is not None \
            else self.ttft_deadline_s
        total = r.deadline_s if r.deadline_s is not None else self.deadline_s
        return ttft, total

    def _resolve_peak(self) -> float:
        """Per-device peak FLOP/s for the serve_mfu gauge: the ctor
        override wins, else the obs.cost device-kind table (resolved
        once) at the ENGINE's matmul precision — an fp32 engine anchors
        to the fp32 peak, not the table's bf16 row (ISSUE 19;
        ``precision.mfu_kind`` translates the engine's compute_dtype)."""
        if self._peak is None:
            from .. import precision as _precision

            self._peak = _cost.peak_flops_per_device(
                self.engine.mesh.devices.flat[0], self._peak_flops,
                precision=_precision.mfu_kind(
                    getattr(self.engine.config, "compute_dtype", None)
                ),
            )
        return self._peak

    # -- externally-driven run form (ISSUE 8) ------------------------------
    #
    # `run` is sugar over four primitives so a front door can own the
    # clock: `begin()` arms a fresh run, `submit()` validates and
    # enqueues (any time while armed — externally-timed submission),
    # `tick()` advances exactly one scheduler step, `collect()`
    # finalizes and returns the same (completions, stats) `run`
    # returns. The multi-replica router (serve.router) interleaves
    # `tick()` across replicas round-robin and reads `pressure()` to
    # place traffic; because an idle tick makes NO device calls, a
    # 1-replica externally-driven run is bit-identical to `run` on the
    # same request stream (pinned in tests/test_router.py).

    def begin(self) -> None:
        """Arm an externally-driven run. One run at a time per
        scheduler — ``collect`` (or ``release``, on an abort path)
        disarms it."""
        if self._st is not None:
            raise RuntimeError(
                "a run is already armed on this scheduler; collect() or "
                "release() it before begin()"
            )
        st = _RunState(self.engine.config.slots)
        st.deadlines_on = (self.ttft_deadline_s is not None
                           or self.deadline_s is not None)
        self._st = st

    def submit(self, r: Request) -> None:
        """Validate and enqueue one request into the armed run. The
        queue stays (arrival, id)-sorted whatever the submission order
        (the fast path — the router submits streams pre-sorted — is a
        plain append)."""
        st = self._require_run()
        self._validate(r)
        if r.id in st.seen_ids:
            raise ValueError(f"duplicate request id {r.id}")
        st.seen_ids.add(r.id)
        st.deadlines_on = st.deadlines_on or (
            r.ttft_deadline_s is not None or r.deadline_s is not None
        )
        last = st.pending[-1] if st.pending else None
        if last is not None and (r.arrival, r.id) < (last.arrival, last.id):
            st.pending = collections.deque(
                sorted([*st.pending, r], key=lambda q: (q.arrival, q.id))
            )
        else:
            st.pending.append(r)
        with span("serve.submit", self.tracer, req=int(r.id)) as sp:
            if self.tracer:
                self.tracer.event(
                    "submit", t=sp.t0, req=int(r.id),
                    prompt_len=int(np.asarray(r.prompt).shape[0]),
                    arrival=int(r.arrival),
                    max_new_tokens=int(r.max_new_tokens),
                )

    def _require_run(self) -> _RunState:
        if self._st is None:
            raise RuntimeError("no armed run: call begin() first")
        return self._st

    @property
    def idle(self) -> bool:
        """True when a tick would have nothing to do — no occupant and
        nothing pending. A request pending at a FUTURE arrival still
        counts as work (the tick loop fast-forwards to it)."""
        st = self._st
        if st is None:
            return True
        return not st.pending and all(o is None for o in st.occupant)

    def pressure(self) -> Pressure:
        """Non-destructive load probe (see :class:`Pressure`): safe at
        any time, armed run or not, and never perturbs queue, LRU or
        page state — the router's placement signal."""
        eng = self.engine
        occupied = active = waiting = total = 0
        st = self._st
        if st is not None:
            occupied = sum(o is not None for o in st.occupant)
            active = int(st.active.sum())
            for q in st.pending:  # (arrival, id)-sorted: early break
                if q.arrival > st.step:
                    break
                waiting += 1
            total = len(st.pending)
        return Pressure(
            occupied_slots=occupied,
            active_slots=active,
            waiting_eligible=waiting,
            pending_total=total,
            pages_free=int(eng.pages.free) if eng.paged else 0,
            pages_available=int(eng.pages.available) if eng.paged else 0,
            prefix_entries=len(eng.prefix) if eng.prefix is not None else 0,
        )

    def waiting_eligible_requests(self) -> list[Request]:
        """The queued requests whose arrival has come but which hold no
        slot yet, in admission (FIFO) order — the fleet controller's
        preemption-trigger probe (ISSUE 13). Read-only, like
        :meth:`pressure`."""
        st = self._st
        if st is None:
            return []
        out = []
        for q in st.pending:  # (arrival, id)-sorted: early break
            if q.arrival > st.step:
                break
            out.append(q)
        return out

    def occupant_requests(self) -> list[tuple[int, Request, bool]]:
        """``(slot, request, active)`` for every occupied slot — the
        controller's preemption-victim probe (only ACTIVE occupants are
        preemptable; a mid-prefill slot has no decode cursor to move).
        Read-only."""
        st = self._st
        if st is None:
            return []
        return [(s, r, bool(st.active[s]))
                for s, r in enumerate(st.occupant) if r is not None]

    # -- cross-replica preemption (ISSUE 13) --------------------------------

    def preempt(self, request_id: int,
                *, path: str = "preempt") -> PreemptedRequest:
        """Lift an ACTIVE (mid-decode) occupant out of the armed run for
        resumption on another scheduler (``adopt``): serialize its
        resident pages host-side, free its slot — pages decref (shared
        prefix pages survive on their entry's reference), any unused
        admission reservation returns, pinned prefix refs release — and
        forget the occupant WITHOUT recording a completion (it completes
        exactly once, on the adopting scheduler). Paged engines only:
        slot-independent refcounted pages are what make the hand-off a
        serialize/deserialize, not a recompute — the resumed tokens are
        bit-identical by construction (pinned in tests/test_fleet.py).

        Host byte plane (ISSUE 20): the dumped pages' host traffic
        lands in ``handoff_bytes_total{path=}`` via the engine's
        ``kv_row_bytes`` oracle — counted ONCE per round trip, on this
        (dump) side; ``adopt`` moves the same bytes back down and does
        not count again, so a preempt→adopt round trip on one registry
        reads exactly the oracle. ``path`` labels who asked: a direct
        controller preemption ("preempt") or a disagg prefill→decode
        transfer ("disagg")."""
        st = self._require_run()
        eng = self.engine
        if not eng.paged:
            raise RuntimeError(
                "preempt needs the paged KV layout (page_size > 0) — "
                "contiguous slots have no slot-independent pages to "
                "hand off"
            )
        for s in range(eng.config.slots):
            r = st.occupant[s]
            if r is not None and r.id == request_id:
                break
        else:
            raise KeyError(
                f"request {request_id} occupies no slot on this scheduler"
            )
        if not st.active[s]:
            raise RuntimeError(
                f"request {request_id} is mid-prefill, not mid-decode — "
                "only active occupants carry a resumable decode cursor"
            )
        k, v, pos = eng.dump_slot_pages(s)
        if self.registry is not None:
            self.registry.counter(
                "handoff_bytes_total",
                help="KV bytes moved through the host, by hand-off path",
            ).inc(eng.handoff_bytes(int(pos.shape[0])), path=path)
        pre = PreemptedRequest(
            request=r,
            generated=list(st.generated[s]),
            last_token=int(st.last_tokens[s]),
            lengths=int(st.lengths[s]),
            admitted_at=int(st.admitted_at[s]),
            eligible_wall=st.eligible_wall[r.id],
            k=k, v=v, pos=pos,
        )
        st.active[s] = False
        st.occupant[s] = None
        # The id no longer lives here — and may legitimately come back
        # (a later crash of the adopting replica requeues it anywhere).
        st.seen_ids.discard(r.id)
        eng.release_slot(s)
        if st.held_entry[s] >= 0:
            eng.prefix_release(st.held_entry[s])
            st.held_entry[s] = -1
        if self.tracer:
            self.tracer.event("preempt", req=int(r.id), slot=s,
                              step=st.step, tokens=len(pre.generated))
        return pre

    def adopt(self, pre: PreemptedRequest) -> int:
        """Install a preempted request into a free slot of the armed
        run, resuming exactly where the source left off: its serialized
        pages become fresh resident pages (``engine.load_slot_pages``),
        the decode cursor (``lengths``/``last_token``) carries over, and
        the sampling key — (seed, request_id, token_index) only — makes
        the continuation's tokens bit-identical to an unpreempted run.
        Reserves the request's remaining worst case like a normal
        admission (reclaiming zero-ref prefix entries if short).
        Returns the slot."""
        st = self._require_run()
        eng = self.engine
        if not eng.paged:
            raise RuntimeError(
                "adopt needs the paged KV layout (page_size > 0)"
            )
        r = pre.request
        if r.id in st.seen_ids:
            raise ValueError(
                f"adopt: request id {r.id} already seen on this scheduler"
            )
        slot = next((s for s in range(eng.config.slots)
                     if st.occupant[s] is None), None)
        if slot is None:
            raise RuntimeError("adopt: no free slot on this scheduler")
        p = int(np.asarray(r.prompt).shape[0])
        need = eng.pages_needed(p + r.max_new_tokens)
        if eng.pages.available < need and not eng.reclaim_pages(need):
            raise RuntimeError(
                f"adopt: request {r.id} needs {need} pages but only "
                f"{eng.pages.available} are available — the controller "
                "must check pages_available before choosing this replica"
            )
        eng.reserve_pages(slot, need)
        eng.load_slot_pages(slot, pre.k, pre.v, pre.pos)
        st.seen_ids.add(r.id)
        st.occupant[slot] = r
        st.active[slot] = True
        st.generated[slot] = list(pre.generated)
        st.lengths[slot] = pre.lengths
        st.last_tokens[slot] = pre.last_token
        st.req_ids[slot] = r.id
        st.admitted_at[slot] = pre.admitted_at
        st.prefilled[slot] = p
        st.store_after[slot] = False
        st.held_entry[slot] = -1
        st.eligible_wall[r.id] = pre.eligible_wall
        st.deadlines_on = st.deadlines_on or (
            r.ttft_deadline_s is not None or r.deadline_s is not None
        )
        if self.tracer:
            self.tracer.event("resume", req=int(r.id), slot=slot,
                              step=st.step, tokens=len(pre.generated))
        return slot

    def abandon(self) -> tuple[dict[int, Completion], list[Request],
                               list[Request]]:
        """Crash harvest (ISSUE 13, ``serve.controller``): hand back the
        armed run's DRIVER-side bookkeeping — completions already
        finished, the requests resident in slots (in-flight, their
        device state lost), and the still-queued requests — and disarm
        WITHOUT touching the engine: a crashed replica's device state is
        gone, the engine is discarded wholesale with its page pool, so
        there is nothing to release. The host ledger survives a replica
        crash exactly as a real front door's would."""
        st = self._require_run()
        inflight = [r for r in st.occupant if r is not None]
        queued = list(st.pending)
        done = dict(st.done)
        self._st = None
        return done, inflight, queued

    def collect(self) -> tuple[dict[int, Completion], ServeStats]:
        """Finalize the armed run: flush the run-total counters into
        the registry and return ``(completions, stats)`` exactly as
        :meth:`run` would. Disarms the run."""
        st = self._require_run()
        latency = st.decode_timer.stats()
        if self.registry is not None:
            reg = self.registry
            reg.counter("serve_prefix_lookups_total").inc(st.lookups)
            reg.counter("serve_prefix_hits_total").inc(st.hits)
            reg.counter("serve_prefill_tokens_saved_total").inc(st.saved)
        stats = ServeStats(
            prefill_tokens=st.prefill_timer.total_images,
            prefill_s=st.prefill_timer.total_s,
            decode_tokens=st.decode_timer.total_images,
            decode_steps=latency.steps,
            decode_s=st.decode_timer.total_s,
            slots=self.engine.config.slots,
            latency=latency,
            ttft=StepStats.from_times(st.ttfts),
            itl=StepStats.from_times(st.itls),
            prefix_lookups=st.lookups,
            prefix_hits=st.hits,
            prefill_tokens_saved=st.saved,
        )
        self._st = None
        return st.done, stats

    def release(self) -> None:
        """Disarm an aborted run, dropping anything it still pins. An
        exception mid-run (device failure, KeyboardInterrupt) must not
        leave pool entries pinned forever on an engine that outlives
        the run — orphaned refs would block every future eviction AND
        registration, and (paged) leaked page references would shrink
        the pool for every future run. No-op after a clean ``collect``
        (normal completion already released everything in
        ``_finish``).

        The paged sweep covers every slot holding mapped pages OR an
        outstanding admission RESERVATION, occupant or not (ISSUE 13
        satellite): an abort between a reservation and its occupant —
        or any state a preempt/adopt left mid-flight — must still
        return the pool byte-whole, reservations included (pinned in
        tests/test_serve_paged.py: free == num_pages and reserved == 0
        after release on an engine without pinned prefix entries)."""
        st = self._st
        if st is None:
            return
        eng = self.engine
        for s in range(eng.config.slots):
            if st.held_entry[s] >= 0:
                eng.prefix_release(st.held_entry[s])
                st.held_entry[s] = -1
            if eng.paged and (st.occupant[s] is not None
                              or int(eng.table_len[s])
                              or int(eng.reserved_for[s])):
                eng.release_slot(s)
        self._st = None

    def run(self, requests) -> tuple[dict[int, Completion], ServeStats]:
        """Serve ``requests`` to completion. Admission order is (arrival,
        id) — a deterministic queue, so runs are reproducible. Every
        request is validated BEFORE any is enqueued, so one malformed
        request fails the whole call with no partial state."""
        for r in requests:
            self._validate(r)
        ids = [r.id for r in requests]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate request ids in {ids}")
        self.begin()
        try:
            for r in sorted(requests, key=lambda r: (r.arrival, r.id)):
                self.submit(r)
            while not self.idle:
                self.tick()
            return self.collect()
        finally:
            self.release()

    # -- the tick body ------------------------------------------------------

    def _finish(self, st: _RunState, s: int, status: str = "ok") -> None:
        eng = self.engine
        tr = self.tracer
        reg = self.registry
        r = st.occupant[s]
        st.done[r.id] = Completion(
            id=r.id,
            prompt_len=int(np.asarray(r.prompt).shape[0]),
            tokens=list(st.generated[s]),
            admitted_step=int(st.admitted_at[s]),
            finished_step=st.step,
            status=status,
        )
        st.active[s] = False
        st.occupant[s] = None
        pages_held = int(eng.table_len[s]) if eng.paged else 0
        if eng.paged:
            # Page references drop (shared prefix pages survive on
            # their entry's reference) and any unused reservation
            # returns — eviction and completion are the same
            # bookkeeping, so a deadline eviction can never leak
            # pool capacity.
            eng.release_slot(s)
        if st.held_entry[s] >= 0:
            # Deadline eviction releases pinned prefix refs exactly
            # like normal completion — an evicted request can never
            # wedge the pool.
            eng.prefix_release(st.held_entry[s])
            st.held_entry[s] = -1
        if tr:
            # Completion IS the eviction: the slot frees here.
            # kv_pages_held records the request's peak residency at
            # completion (ISSUE 7 satellite — 0 on the contiguous
            # layout, where residency is the fixed capacity).
            tr.event("complete", req=int(r.id), slot=s, step=st.step,
                     tokens=len(st.generated[s]), status=status,
                     kv_pages_held=pages_held)
        if reg is not None:
            if status == "deadline_exceeded":
                reg.counter("serve_deadline_exceeded_total").inc()
            else:
                reg.counter("serve_requests_completed_total").inc()

    def _expire_queued(self, st: _RunState, r: Request, status: str) -> None:
        """Remove a never-admitted request from the queue with a
        structured outcome (shed at admission, or expired while
        waiting) — it held no slot and pinned nothing."""
        st.pending.remove(r)
        st.done[r.id] = Completion(
            id=r.id,
            prompt_len=int(np.asarray(r.prompt).shape[0]),
            tokens=[], admitted_step=-1, finished_step=st.step,
            status=status,
        )
        if self.tracer:
            self.tracer.event(status, req=int(r.id), step=st.step)
        if self.registry is not None:
            self.registry.counter(
                "serve_shed_total" if status == "shed"
                else "serve_deadline_exceeded_total"
            ).inc()

    def _finished(self, st: _RunState, s: int, token: int) -> bool:
        return (len(st.generated[s]) >= st.occupant[s].max_new_tokens
                or (self.eos_id is not None and token == self.eos_id))

    def _speculate_decode(self, st: _RunState, step: int):
        """The speculative decode phase (ISSUE 15, ``serve.speculate``):
        still exactly ONE batched decode call per tick — the same
        compiled program the plain path runs — but FREE slots become
        draft LANES: lane ``i`` aliases the speculating slot's pages
        (``engine.alias_slot_pages``, incref only), feeds draft token
        ``i`` at position ``n + 1 + i``, and its returned sample IS the
        target model's greedy token for that position (the decode
        program's per-slot math is row-independent — the continuous-
        batching determinism pin — so every lane row is bitwise the
        sequential step's). Greedy-accept keeps the longest matching
        draft prefix plus the first mismatch (the true next token), so
        output is BIT-IDENTICAL to plain decode; rejected lanes leave
        rows only BEYOND the new frontier (position-masked invisible,
        overwritten by the next step that reaches them). Returns
        ``(decode_s, itl_s, mfu_val)`` for the tick's anomaly feed."""
        eng = self.engine
        cfg = eng.config
        S = cfg.slots
        tr = self.tracer
        reg = self.registry
        gp = self._goodput
        k = cfg.speculate_k
        last = st.last_tokens.copy()
        lengths = st.lengths.copy()
        req_ids = st.req_ids.copy()
        active = st.active.copy()
        free = [s for s in range(S) if st.occupant[s] is None]
        lanes_of: dict[int, tuple[list[int], np.ndarray]] = {}
        proposed = 0
        for s in range(S):
            if not st.active[s] or not free:
                continue
            r = st.occupant[s]
            remaining = r.max_new_tokens - len(st.generated[s])
            if remaining < 2:
                # One token to go: a draft could only propose tokens
                # the budget forbids emitting.
                continue
            prompt = np.asarray(r.prompt, np.int32)
            ctx = np.concatenate(
                [prompt, np.asarray(st.generated[s], np.int32)]
            )
            draft = propose_draft(
                ctx, min(k, remaining - 1, len(free)),
                method=cfg.speculate_method,
                prompt_len=int(prompt.shape[0]),
            )
            if not draft.size:
                continue  # no lookup hit: this slot rides plain
            n = int(st.lengths[s])
            lanes = free[: draft.size]
            del free[: draft.size]
            for i, lane in enumerate(lanes):
                eng.alias_slot_pages(lane, s, n + int(draft.size) + 1)
                active[lane] = True
                last[lane] = int(draft[i])
                lengths[lane] = n + 1 + i
                req_ids[lane] = r.id
            lanes_of[s] = (lanes, draft)
            proposed += int(draft.size)
        n_active = int(st.active.sum())
        n_lanes = sum(len(lanes) for lanes, _ in lanes_of.values())
        # Computed BEFORE finishes mutate occupancy — the decode_tick
        # `reqs` attribute lists the REAL slots that decoded, exactly
        # as the plain path does (lanes are compute, not requests).
        reqs_now = [int(st.req_ids[i]) for i in range(S) if st.active[i]]
        with span("serve.decode", tr) as sp:
            t0 = sp.t0 if tr else time.perf_counter()
            nxt, _ = eng.decode(last, lengths, req_ids, active)
            now = sp.t1 = time.perf_counter()
            sp.set(pages=_attended_pages(eng))
        dt = now - t0
        # Lane teardown is pure decref (the source slot's own refs keep
        # every page live) — done before bookkeeping so no later raise
        # can leak an aliased table.
        for lanes, _ in lanes_of.values():
            for lane in lanes:
                eng.release_slot(lane)
        chained = st.last_decode_done is not None
        itl_s = None
        if chained:
            st.itls.append(now - st.last_decode_done)
            itl_s = st.itls[-1]
        st.last_decode_done = now
        emitted_total = 0
        accepted_total = 0
        for s in range(S):
            if not st.active[s]:
                continue
            lanes, draft = lanes_of.get(s, (None, None))
            if lanes is None:
                # No draft for this slot: its own decode row advanced
                # it exactly one token, the plain way.
                st.lengths[s] += 1
                tok = int(nxt[s])
                st.generated[s].append(tok)
                st.last_tokens[s] = tok
                emitted_total += 1
                if self._finished(st, s, tok):
                    self._finish(st, s)
                continue
            # verified[0] is the slot's own next token, verified[1 + i]
            # lane i's — the model's greedy answer at each position.
            verified = [int(nxt[s])] + [int(nxt[lane]) for lane in lanes]
            a = greedy_accept(draft, verified)
            emitted = 0
            for tok in verified[: a + 1]:
                st.lengths[s] += 1
                st.generated[s].append(tok)
                st.last_tokens[s] = tok
                emitted += 1
                if self._finished(st, s, tok):
                    self._finish(st, s)
                    break  # eos/budget truncates the rest of the block
            emitted_total += emitted
            # Only drafts actually EMITTED count as accepted (a draft
            # "matching" past an eos was never served).
            accepted_total += min(a, emitted)
        st.decode_timer.add(dt, images=emitted_total)
        decode_s = dt
        if gp is not None:
            gp.add("decode", dt)
        if tr:
            tr.complete("decode_tick", t0, now, step=step,
                        n_active=n_active, chained=chained,
                        reqs=reqs_now, spec_lanes=n_lanes,
                        spec_emitted=emitted_total)
        mfu_val = None
        if reg is not None:
            reg.counter("serve_decode_tokens_total").inc(emitted_total)
            reg.histogram("serve_decode_step_seconds").observe(dt)
            if chained:
                reg.histogram("serve_itl_seconds").observe(st.itls[-1])
            if proposed:
                # The measured acceptance ledger (ISSUE 15): accepted /
                # proposed is the rate that says whether k paid.
                reg.counter("speculate_proposed_total").inc(proposed)
                reg.counter("speculate_accepted_total").inc(
                    accepted_total
                )
            fpt = _cost.serve_decode_flops_per_token(
                cfg.spec, eng.last_attend_width
            )
            reg.gauge("serve_flops_per_token").set(fpt)
            # Honest verify accounting (obs.cost): lanes COMPUTE at the
            # attended width whether or not their draft is accepted —
            # the MFU numerator prices real + lane rows, while the
            # token counters above carry only what was emitted.
            mfu_val = _cost.mfu(
                _cost.serve_speculate_verify_flops(
                    cfg.spec, n_active + n_lanes, eng.last_attend_width
                ),
                dt, int(eng.mesh.devices.size), self._resolve_peak(),
            )
            reg.gauge("serve_mfu").set(mfu_val)
        return decode_s, itl_s, mfu_val

    def tick(self) -> None:
        """One scheduler step of the armed run: stamp eligibility /
        shed / expire, admit into free slots, prefill under the chunk
        budget, one batched decode, per-tick telemetry — exactly the
        loop body ``run`` iterates until idle. An idle tick (nothing
        eligible, nothing active) makes NO device calls, which is what
        lets an external driver insert clock-alignment ticks without
        perturbing the device-call sequence.

        The whole tick is one ``serve.tick`` span (obs.trace.span), each
        device call inside it a ``serve.prefill`` / ``serve.decode``."""
        with span("serve.tick", self.tracer):
            self._tick()

    def _tick(self) -> None:
        st = self._require_run()
        eng = self.engine
        cfg = eng.config
        S = cfg.slots
        tr = self.tracer
        reg = self.registry
        inj = self.injector
        # Goodput attribution (ISSUE 11): the whole tick is bracketed;
        # device sub-brackets (prefill/decode/prefix-copy — the SAME
        # StepTimer values the histograms observe) are attributed as
        # they close and the residual lands in host/idle at end_tick.
        gp = self._goodput
        if gp is not None:
            gp.begin_tick()
        decode_s = itl_s = mfu_val = None
        chunk = cfg.prefill_chunk
        # Unset budget defaults to ONE chunk per tick — maximum decode
        # interleaving; chunking with an unmetered tick would run every
        # chunk back-to-back and reintroduce the whole-prompt stall.
        budget0 = cfg.prefill_budget or chunk
        step = st.step
        # TTFT clock starts the first tick a request is eligible
        # (arrival reached), whether or not a slot is free — the
        # queueing delay is part of time-to-first-token.
        now = time.perf_counter()
        # Admission shedding decides ONCE, at first eligibility:
        # outstanding work (occupied slots + already-waiting
        # eligibles) at or past the threshold refuses the newcomer
        # with a structured "shed" — overload degrades the newest
        # arrivals instead of collapsing every admitted request's
        # ITL.
        outstanding = -1
        if self.shed_threshold is not None:
            outstanding = sum(o is not None for o in st.occupant) + sum(
                1 for q in st.pending
                if q.arrival <= step and q.id in st.eligible_wall
            )
        shed_now = []
        for r in st.pending:
            if r.arrival > step:
                break  # pending is (arrival, id)-sorted
            if r.id not in st.eligible_wall:
                if self.shed_threshold is not None \
                        and outstanding >= self.shed_threshold \
                        and not r.shed_exempt:
                    shed_now.append(r)
                    continue
                st.eligible_wall[r.id] = now
                outstanding += 1
                if tr:
                    # Stamped with the SAME `now` the TTFT clock
                    # starts from — the derived-TTFT exactness pin.
                    tr.event("eligible", t=now, req=int(r.id), step=step)
        # The shed/deadline sweep is attributed as "shed" overhead
        # (work=False: bookkeeping, not device work) — only bracketed
        # when it can actually do something, so the common fast path
        # pays no clock reads.
        t_shed0 = (time.perf_counter()
                   if gp is not None and (shed_now or st.deadlines_on)
                   else None)
        for r in shed_now:
            self._expire_queued(st, r, "shed")
        if st.deadlines_on:
            # Expiry sweep: waiting requests past any applicable
            # deadline never admit; occupied slots past theirs evict
            # (partial tokens kept, prefix pins released in _finish).
            expired = []
            for r in st.pending:
                if r.arrival > step:
                    break
                t0 = st.eligible_wall.get(r.id)
                if t0 is None:
                    continue
                lims = [v for v in self._deadline_for(r) if v is not None]
                if lims and now - t0 > min(lims):
                    expired.append(r)
            for r in expired:
                self._expire_queued(st, r, "deadline_exceeded")
            for s in range(S):
                r = st.occupant[s]
                if r is None:
                    continue
                ttft, total = self._deadline_for(r)
                # Pre-first-token both deadlines bound the wait;
                # once decoding, only the total deadline applies.
                lims = [v for v in ((ttft, total) if not st.active[s]
                                    else (total,)) if v is not None]
                if lims and now - st.eligible_wall[r.id] > min(lims):
                    self._finish(st, s, status="deadline_exceeded")
        if t_shed0 is not None:
            gp.add("shed", time.perf_counter() - t_shed0, work=False)
        # Admit: claim every free slot whose turn has come. With the
        # prefix cache, admission itself is only the (optional) row
        # copy (contiguous) or table mapping (paged) — prompt
        # compute happens in the prefill phase below. On the paged
        # pool, admission FIRST checks "enough free pages" for the
        # request's worst case (prompt + max_new, minus the full
        # pages a prefix hit shares) and RESERVES them — capacity
        # pools across slots instead of a per-slot worst-case ring.
        # The queue stays strictly FIFO: when the head cannot fit,
        # nothing behind it admits either (deterministic, and no
        # small-request starvation of the long head).
        for s in range(S):
            if st.occupant[s] is not None or not st.pending \
                    or st.pending[0].arrival > step:
                continue
            r = st.pending[0]
            p = int(np.asarray(r.prompt).shape[0])

            def probe():
                # The match is PURE (no LRU stamp), so probing before
                # admission is decided cannot perturb the index.
                if eng.prefix is None:
                    return -1, 0, 0
                entry, full = eng.prefix.match(r.prompt)
                hit = min(full, p - 1)
                return entry, full, hit if hit >= MIN_PREFIX_HIT else 0

            entry, full, hit = probe()
            if eng.paged:
                while True:
                    need = eng.pages_needed(p + r.max_new_tokens) \
                        - hit // eng.page_size
                    if eng.can_admit(need):
                        break
                    if not eng.reclaim_pages(need):
                        need = -1
                        break
                    # Reclaim may have evicted the matched entry
                    # itself (it was zero-ref) — re-probe so the
                    # fetch below can never reference a ghost and
                    # the reservation covers the (possibly shrunk)
                    # hit. Entries strictly decrease per round, so
                    # this terminates.
                    entry, full, hit = probe()
                if need < 0:
                    break  # head waits for pages; FIFO holds
                eng.reserve_pages(s, need)
            st.pending.popleft()
            st.occupant[s] = r
            st.generated[s] = []
            st.admitted_at[s] = step
            base = 0
            st.store_after[s] = False
            if tr:
                tr.event("admit", req=int(r.id), slot=s, step=step)
            if eng.prefix is not None:
                st.lookups += 1
                if hit >= MIN_PREFIX_HIT:
                    timed = tr or gp is not None
                    t0 = time.perf_counter() if timed else 0.0
                    copied = eng.prefix_fetch(entry, hit, s)
                    t1 = time.perf_counter() if timed else 0.0
                    if gp is not None:
                        gp.add("prefix_copy", t1 - t0)
                    if tr:
                        # Contiguous: a pool->slot row gather of all
                        # `hit` rows. Paged: zero-copy page mapping;
                        # copied_rows is the CoW partial tail page
                        # only (< page_size — the zero-copy pin
                        # asserts on exactly this attribute).
                        tr.complete(
                            "prefix_map" if eng.paged
                            else "prefix_copy",
                            t0, t1,
                            req=int(r.id), slot=s, rows=hit,
                            copied_rows=int(copied),
                        )
                    st.held_entry[s] = entry
                    base = hit
                    st.hits += 1
                    st.saved += hit
                # Register once the whole prompt is resident IF the
                # cache covers less than half of it: a true miss, or
                # a prompt extending its prefix meaningfully (the
                # multi-turn case — context + a long continuation).
                # Re-registering every hitting prompt would thrash
                # the pool instead: each unique-tail registration
                # evicts another family's live prefix, and the hit
                # rate collapses (measured in serve_bench's
                # prefix_compare before this policy existed).
                st.store_after[s] = full < max(p // 2, MIN_PREFIX_HIT)
            st.prefilled[s] = base
            # While this slot is mid-prefill, decode ticks still
            # compute it (fixed shapes) and write one PAD_POS row at
            # `lengths[s]` — keep that pointed at the NEXT chunk's
            # first row (overwritten by the chunk anyway), never at
            # a stale value that could stomp rows already resident.
            st.lengths[s] = base
        # Prefill: advance every occupied-but-not-active slot, whole
        # prompt at once when chunking is off, else chunk-at-a-time
        # under the shared per-tick token budget.
        budget = budget0
        prefilled_any = False
        for s in range(S):
            r = st.occupant[s]
            if r is None or st.active[s]:
                continue
            if inj is not None and inj.stalls(r.id):
                # Injected stall (resilience.faults): the prefill
                # never advances — the hung-upstream failure mode a
                # deadline must evict (validated at submit: a
                # stalled request always has one).
                continue
            prompt = np.asarray(r.prompt, np.int32)
            p = int(prompt.shape[0])
            while st.prefilled[s] < p:
                todo = p - int(st.prefilled[s])
                n = todo if not chunk else min(chunk, todo)
                if budget0 and budget < n:
                    break  # out of tick budget; resume next tick
                base = int(st.prefilled[s])
                bucket = eng.prefill_bucket(n)
                with span("serve.prefill", tr, req=int(r.id), n=n,
                          bucket=bucket) as sp:
                    with st.prefill_timer.step(images=n):
                        tok, _ = eng.prefill(
                            prompt[base:base + n], slot=s,
                            request_id=r.id, base=base,
                        )
                    if eng.last_counters:
                        sp.set(**eng.last_counters)
                    if tr:
                        # One bracket, two names: the span shares
                        # prefill_chunk's clock reads.
                        sp.t1 = time.perf_counter()
                        tr.complete("prefill_chunk", sp.t0, sp.t1,
                                    req=int(r.id), slot=s, base=base, n=n)
                if gp is not None:
                    # The SAME bracket the StepTimer recorded — the
                    # attribution and the latency surface cannot
                    # disagree.
                    gp.add("prefill", st.prefill_timer._times[-1])
                if reg is not None:
                    reg.counter("serve_prefill_tokens_total").inc(n)
                    # The SAME bracket value the StepTimer recorded,
                    # so the two latency surfaces cannot disagree.
                    reg.histogram("serve_prefill_seconds").observe(
                        st.prefill_timer._times[-1]
                    )
                    # Analytic prefill cost of the block just computed
                    # (obs.cost, ISSUE 10): the compiled BUCKET's rows
                    # over the cache-wide attend span, amortized per
                    # real token — padding computes too, and the gauge
                    # says so.
                    reg.gauge("serve_prefill_flops_per_token").set(
                        _cost.serve_prefill_flops(
                            cfg.spec, bucket, cfg.capacity
                        ) / n
                    )
                st.prefilled[s] += n
                prefilled_any = True
                st.lengths[s] = st.prefilled[s]  # see admission comment
                if budget0:
                    budget -= n
                if base + n == p:  # prompt complete: first token
                    if eng.prefix is not None and st.store_after[s]:
                        stored = eng.prefix_store(prompt, s)
                        if tr and stored:
                            tr.event("prefix_store", req=int(r.id),
                                     slot=s, rows=p)
                    st.active[s] = True
                    st.lengths[s] = p
                    st.last_tokens[s] = tok
                    st.req_ids[s] = r.id
                    st.generated[s] = [tok]
                    t_first = time.perf_counter()
                    st.ttfts.append(t_first - st.eligible_wall[r.id])
                    if tr:
                        # Same `t_first` as the TTFT sample above —
                        # derive_request_slo recovers it exactly.
                        tr.event("first_token", t=t_first,
                                 req=int(r.id), slot=s, step=step)
                    if reg is not None:
                        reg.histogram("serve_ttft_seconds").observe(
                            st.ttfts[-1]
                        )
                    if self._finished(st, s, tok):
                        self._finish(st, s)
                    break
        if st.active.any() and self.role == "prefill":
            # Disaggregated prefill role (ISSUE 15): first-token slots
            # are HELD for the fleet coordinator's page hand-off — this
            # replica never runs the decode program at all (it stays
            # the matmul-bound full-width-prefill specialist). A held
            # tick makes no device calls; the decode-side ITL chain is
            # someone else's story.
            st.last_decode_done = None
        elif st.active.any() and self.engine.config.speculate_k:
            decode_s, itl_s, mfu_val = self._speculate_decode(st, step)
        elif st.active.any():
            n_active = int(st.active.sum())
            with span("serve.decode", tr) as sp:
                with st.decode_timer.step(images=n_active):
                    nxt, _ = eng.decode(st.last_tokens, st.lengths,
                                        st.req_ids, st.active)
                # The ITL clock's read closes the span's bracket too.
                now = sp.t1 = time.perf_counter()
                sp.set(pages=_attended_pages(eng), **eng.last_counters)
            chained = st.last_decode_done is not None
            if chained:
                # The gap since the previous decode completion —
                # prefill work interleaved between ticks included.
                st.itls.append(now - st.last_decode_done)
                itl_s = st.itls[-1]
            st.last_decode_done = now
            decode_s = st.decode_timer._times[-1]
            if gp is not None:
                gp.add("decode", decode_s)
            if tr:
                # End timestamp == the ITL clock's `now`; `chained`
                # records whether the gap-to-previous counted, so
                # derive_request_slo replays the ITL stream exactly.
                # `reqs` lists the slots' request ids that decoded this
                # tick — the per-request/per-class ITL derivation's
                # input (ISSUE 8: derive_request_slo group_by).
                tr.complete("decode_tick", sp.t0, now, step=step,
                            n_active=n_active, chained=chained,
                            reqs=[int(st.req_ids[i]) for i in range(S)
                                  if st.active[i]])
            if reg is not None:
                reg.counter("serve_decode_tokens_total").inc(n_active)
                reg.histogram("serve_decode_step_seconds").observe(
                    st.decode_timer._times[-1]
                )
                if chained:
                    reg.histogram("serve_itl_seconds").observe(st.itls[-1])
                # Analytic decode cost (obs.cost, ISSUE 10): per-token
                # FLOPs at the width this tick actually attended — the
                # paged bucket's residency, or the contiguous capacity
                # (the paged layout's per-token saving made visible) —
                # and the MFU of the decode step just timed.
                fpt = _cost.serve_decode_flops_per_token(
                    cfg.spec, eng.last_attend_width
                )
                reg.gauge("serve_flops_per_token").set(fpt)
                mfu_val = _cost.mfu(
                    fpt * n_active, st.decode_timer._times[-1],
                    int(eng.mesh.devices.size), self._resolve_peak(),
                )
                reg.gauge("serve_mfu").set(mfu_val)
            for s in range(S):
                if not st.active[s]:
                    continue
                st.lengths[s] += 1  # last_tokens[s] entered the cache
                tok = int(nxt[s])
                st.generated[s].append(tok)
                st.last_tokens[s] = tok
                if self._finished(st, s, tok):
                    self._finish(st, s)
        else:
            # No decoder advanced this tick: the next decode's gap
            # is idle/prefill lead-in, not an inter-token stall.
            st.last_decode_done = None
            if st.deadlines_on and not prefilled_any \
                    and any(o is not None for o in st.occupant):
                # Only stalled/expiring work remains — yield the
                # host briefly instead of spinning the tick loop
                # flat-out until a wall-clock deadline passes.
                time.sleep(0.0005)
        if reg is not None:
            # Per-tick utilization gauges (sampled, last-write-wins
            # in the registry; history lands in the JSONL snapshots).
            depth = 0
            for q in st.pending:  # (arrival, id)-sorted: early break
                if q.arrival > step:
                    break
                depth += 1
            reg.gauge("serve_queue_depth").set(depth)
            reg.gauge("serve_active_slots").set(int(st.active.sum()))
            reg.gauge("serve_occupied_slots").set(
                sum(o is not None for o in st.occupant)
            )
            if eng.prefix is not None:
                reg.gauge("serve_prefix_pool_entries").set(
                    len(eng.prefix)
                )
            if eng.paged:
                # Pool utilization (ISSUE 7 satellite): free pages
                # are the admission headroom, shared pages (ref >=
                # 2) the zero-copy prefix win made visible.
                reg.gauge("serve_kv_pages_free").set(eng.pages.free)
                reg.gauge("serve_kv_pages_shared").set(
                    eng.pages.shared
                )
            # Device memory watermarks (obs.memory, ISSUE 10): a host
            # allocator query, self-latching off on backends without
            # memory_stats — one attribute check per tick after that.
            # Present from the ctor OR a later attach_registry (the
            # bench per-rep swap rebuilds it, ISSUE 11); None only
            # when the registry was installed by a bare attribute
            # write.
            if self._mem is not None:
                self._mem.sample()
            if self.metrics_writer is not None:
                # Rate-limited internally (interval_s): the per-tick
                # gauge HISTORY lands in the JSONL as a time series,
                # not just the final tick's values.
                self.metrics_writer.maybe_flush()
        if self.anomaly is not None:
            # Score this tick's signal vocabulary (obs.anomaly). The
            # detector's registry is validated == self.registry at the
            # ctor, so `depth` above is always bound here. Host-state
            # signals (queue_depth/active_slots/occupied_slots/
            # pages_free) are deterministic functions of the tick
            # clock — the pinned scenarios fire on them; the wall-clock
            # signals (step_time/itl/mfu) ride along for live ops.
            vals: dict = {
                "queue_depth": depth,
                "active_slots": int(st.active.sum()),
                "occupied_slots": sum(o is not None for o in st.occupant),
            }
            if eng.paged:
                vals["pages_free"] = int(eng.pages.free)
            if decode_s is not None:
                vals["step_time"] = decode_s
                if mfu_val is not None:
                    vals["mfu"] = mfu_val
            if itl_s is not None:
                vals["itl"] = itl_s
            self.anomaly.tick(vals)
        if self.slo_monitor is not None:
            # Advance the burn-rate windows one tick (obs.slo): reads
            # only its own registry, so runs without a monitor are
            # untouched.
            self.slo_monitor.tick()
        if gp is not None:
            # Close the tick bracket: residual time (admission,
            # telemetry, the deadline-wait sleep) files under host or
            # idle and the gauges publish — the identity holds every
            # tick.
            gp.end_tick()
        st.step = step + 1
        if all(o is None for o in st.occupant) and st.pending:
            # Idle gap before the next arrival: every intervening
            # step would admit and decode nothing, so jump straight
            # to it instead of spinning one Python iteration per
            # empty step (pending is (arrival, id)-sorted).
            st.step = max(st.step, st.pending[0].arrival)


def _attended_pages(eng) -> int:
    """The page-count bucket of the engine's last decode (0 on the
    contiguous layout, which attends its whole capacity)."""
    return eng.last_attend_width // eng.page_size if eng.paged else 0


def request_slo_samples(records) -> dict[int, tuple[float, list[float]]]:
    """Per-REQUEST SLO raw samples from a run's tracer records:
    ``{request_id: (ttft_seconds, [itl_seconds, ...])}``.

    TTFT is ``first_token.t - eligible.t``. The per-request ITL stream
    is the gaps between that request's consecutive TOKEN emission
    times — its ``first_token`` stamp followed by the end timestamp of
    every ``decode_tick`` whose ``reqs`` attribute lists it (the
    scheduler records exactly the slots that decoded each tick, so a
    request's token times are recoverable without knowing slot
    assignments). Requests that never reached a first token (shed,
    expired in queue) are absent. This is the shared substrate of the
    grouped :func:`derive_request_slo` AND the router's per-class SLO
    attainment — one definition, two consumers (ISSUE 8)."""
    eligible: dict[int, float] = {}
    first: dict[int, float] = {}
    token_times: dict[int, list[float]] = {}
    for rec in records:
        name = rec.get("name")
        attrs = rec.get("attrs", {})
        if name == "eligible":
            eligible.setdefault(attrs["req"], rec["t"])
        elif name == "first_token":
            rid = attrs["req"]
            first[rid] = rec["t"]
            token_times.setdefault(rid, []).append(rec["t"])
        elif name == "decode_tick":
            for rid in attrs.get("reqs", ()):
                token_times.setdefault(rid, []).append(rec["t"])
    out: dict[int, tuple[float, list[float]]] = {}
    for rid, t1 in first.items():
        ts = token_times[rid]
        out[rid] = (t1 - eligible[rid],
                    [b - a for a, b in zip(ts, ts[1:])])
    return out


def derive_request_slo(records, group_by=None):
    """SLO stats derived PURELY from a run's tracer records
    (``Tracer.records`` or a read-back JSONL file).

    ``group_by=None`` (default): returns the run-global ``(ttft, itl)``
    ``StepStats`` pair. Works because the scheduler stamps the
    lifecycle events with the SAME ``perf_counter`` values its own SLO
    math uses: TTFT is ``first_token.t - eligible.t`` per request, ITL
    the gap between consecutive ``decode_tick`` end timestamps whose
    later tick is ``chained`` (an idle/prefill-lead-in tick breaks the
    chain exactly as the live computation's reset does). The result is
    EXACTLY equal — same floats, not approximately — to
    ``ServeStats.ttft``/``.itl`` of the run that produced the records
    (pinned at tp=1 and tp=2 in tests/test_obs.py), which is what makes
    the trace a sufficient record of a run's SLO story.

    ``group_by`` (ISSUE 8 satellite): a dict or callable mapping
    request id -> group label (``None`` drops the request). Returns
    ``{label: (ttft, itl)}`` where both stats pool PER-REQUEST samples
    (:func:`request_slo_samples`) over the group's members and delegate
    to ``StepStats.from_times`` — the single percentile definition the
    whole repo uses. Because the grouped path touches only its own
    members' per-request streams, the result for a group is IDENTICAL
    to filtering the records to that group first and deriving then
    (pinned in tests/test_obs.py): per-class and per-replica breakdowns
    are the same computation, just keyed differently. Per-request ITL
    needs the ``decode_tick`` ``reqs`` attribute (present from ISSUE 8
    on); older traces yield empty grouped ITL.

    Degenerate inputs (ISSUE 10 satellite — SKIP, never raise: the
    derivation is a read-only reporting surface and an empty run is a
    valid run): an empty record list returns zero-filled ``StepStats``
    ungrouped and ``{}`` grouped; a group whose members never reached a
    first token (all shed / expired in queue) is ABSENT from the
    grouped result — absence is the honest answer ("no latency
    evidence"), distinct from a zero-latency entry, and matches
    ``request_slo_samples`` covering served requests only (the router's
    ``ClassReport`` separately counts those members as misses);
    a callable ``group_by`` returning None drops that request from
    every group. All three pinned in tests/test_obs.py."""
    if group_by is None:
        eligible: dict[int, float] = {}
        ttfts: list[float] = []
        itls: list[float] = []
        prev: float | None = None
        for rec in records:
            name = rec.get("name")
            attrs = rec.get("attrs", {})
            if name == "eligible":
                eligible.setdefault(attrs["req"], rec["t"])
            elif name == "first_token":
                ttfts.append(rec["t"] - eligible[attrs["req"]])
            elif name == "decode_tick":
                if attrs.get("chained") and prev is not None:
                    itls.append(rec["t"] - prev)
                prev = rec["t"]
        return StepStats.from_times(ttfts), StepStats.from_times(itls)
    key_of = group_by if callable(group_by) else group_by.get
    grouped: dict[object, tuple[list[float], list[float]]] = {}
    for rid, (ttft, itls_r) in request_slo_samples(records).items():
        key = key_of(rid)
        if key is None:
            continue
        g = grouped.setdefault(key, ([], []))
        g[0].append(ttft)
        g[1].extend(itls_r)
    return {
        k: (StepStats.from_times(tt), StepStats.from_times(ii))
        for k, (tt, ii) in grouped.items()
    }
