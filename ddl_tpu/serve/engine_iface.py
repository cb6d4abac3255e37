"""The serve engine interface — the narrow surface the control plane
actually calls (ISSUE 18).

Every control decision in the serve stack — router placement, door
shedding, autoscale/drain/crash-heal, preemption, disagg hand-off, SLO
burn, anomaly edges — is deterministic host logic on a tick clock; only
the engine underneath touches a device.  :class:`ServeEngine` is the
written-down contract of that boundary: the attributes and methods
``Scheduler`` / ``Router`` / ``FleetController`` / ``DisaggCoordinator``
read, and nothing else.  The real engines
(:class:`~ddl_tpu.serve.engine.InferenceEngine`, ``kind == "real"``, and
its second family :class:`~ddl_tpu.serve.hybrid_engine.HybridEngine`)
and the twin (:class:`~ddl_tpu.serve.sim.CostModelEngine`, ``kind ==
"sim"``: no arrays, virtual time) implement its host half ONCE, by
inheriting :class:`~ddl_tpu.serve.host.EngineHost`, which names the two
places where an engine's device comes in.

The contract is structural (``typing.Protocol``): the control plane
stays duck-typed, and tests/test_twin.py asks every name the scheduler,
the controller and the CLI read of all three classes.  Because every
control decision reads only this surface, any engine satisfying it
replays the identical controller event timeline — the tick-for-tick
parity pin in tests/test_twin.py.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

__all__ = ["ServeEngine", "engine_kind"]


@runtime_checkable
class ServeEngine(Protocol):
    """What the control plane may touch on an engine.

    Attributes (host state the scheduler/controller read directly):

    * ``kind`` — ``"real"`` or ``"sim"``; surfaced in ``fleet_summary``
      and ``/healthz`` so a twin run can never masquerade as measured.
    * ``config`` — the :class:`~ddl_tpu.serve.engine.ServeConfig`.
    * ``paged`` / ``page_size`` / ``max_pages`` / ``num_pages`` — KV
      layout geometry (all zero when contiguous).
    * ``pages`` — the :class:`~ddl_tpu.serve.cache.PagePool` (paged).
    * ``tables`` / ``table_len`` / ``reserved_for`` — block tables.
    * ``prefix`` — the :class:`~ddl_tpu.serve.prefix.PrefixIndex` or
      ``None``; ``page_copies`` — CoW tail-copy counter.
    * ``mesh`` — exposes ``.devices.flat`` (memory sampler, peak-FLOPs
      lookup) and ``.devices.size`` (MFU denominator).
    * ``params`` — opaque; replicas share one tree via
      ``placed_params`` (may be ``None`` for a cost-model engine).
    * ``compile_hook`` — set by the scheduler when a registry is on;
      the engine calls ``hook(kind, key)`` at every DISTINCT program
      build (one shape signature each: builds and compiles are 1:1).
    * ``last_attend_width`` — rows the last decode attended (the
      paged-aware ``serve_flops_per_token`` denominator).
    * ``last_counters`` — what the last program counted beyond its
      tokens (``{}`` for the dense family and the twin), set as
      attributes on the scheduler's ``serve.prefill`` / ``serve.decode``.
    """

    kind: str

    # -- compute ticks ------------------------------------------------------
    # Both return a pair ``(sampled, logits)``: ``sampled`` is the next
    # token (prefill: an int; decode: ``np [slots]``), all that leaves
    # the device and all the control plane reads. ``logits`` is ``None``
    # unless the caller passed ``want_logits=True`` IN THAT CALL (parity
    # tests, scoring; never the scheduler, never warmup): then prefill
    # gives ``np [t, vocab]`` of the block's rows (the hybrid family:
    # ``[1, vocab]``, its last row) and decode ``np [slots, vocab]``.
    # What nobody asked for is neither fetched nor, in a prefill,
    # computed beyond the row the token is sampled from.
    def prefill(self, prompt, *, slot: int, request_id: int, base: int = 0,
                _bucket: int | None = None, want_logits: bool = False): ...

    def decode(self, last_tokens, lengths, request_ids, active, *,
               _pages: int | None = None, want_logits: bool = False): ...

    # -- shape/bucket ladders ----------------------------------------------
    def prefill_bucket(self, prompt_len: int) -> int: ...

    def decode_page_bucket(self, pages: int) -> int: ...

    # -- paged page management ---------------------------------------------
    def pages_needed(self, rows: int) -> int: ...

    def reserve_pages(self, slot: int, n: int) -> None: ...

    def can_admit(self, need: int) -> bool: ...

    def reclaim_pages(self, need: int) -> bool: ...

    def release_slot(self, slot: int) -> None: ...

    # -- cross-replica hand-off (preempt / crash requeue / disagg) ----------
    def dump_slot_pages(self, slot: int): ...

    def load_slot_pages(self, slot: int, k, v, pos) -> list[int]: ...

    def alias_slot_pages(self, dst_slot: int, src_slot: int,
                         rows: int) -> int: ...

    def handoff_bytes(self, n_pages: int) -> int: ...

    # -- prefix cache -------------------------------------------------------
    def prefix_fetch(self, entry_id: int, n: int, slot: int) -> int: ...

    def prefix_release(self, entry_id: int) -> None: ...

    def prefix_store(self, prompt, slot: int) -> bool: ...

    # -- lifecycle ----------------------------------------------------------
    def reset(self) -> None: ...


def engine_kind(engine) -> str:
    """``"real"`` or ``"sim"`` for any engine object.  Pre-interface
    engines (no ``kind`` attribute) are real by construction — the
    cost-model engine is the only one that ever says otherwise, so a
    missing attribute defaults loud-side-safe to ``"real"``."""
    return str(getattr(engine, "kind", "real"))
