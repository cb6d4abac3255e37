"""Cost-model serve engine — the digital twin's device-free engine
(ISSUE 18, ROADMAP item 5).

:class:`CostModelEngine` implements the :class:`ServeEngine` contract
with **no arrays**. Its host half is the real engines' own
(:class:`~ddl_tpu.serve.host.EngineHost`: config check, page pool, block
tables, reservations, prefix index, bucket ladders), inherited, with
neither device hook filled in. What is written here is what stands in
for the device: a deterministic token hash, the ``rows`` a dump's
``pos`` is made from, and a per-phase *virtual time* charge (prefill
per token, decode per tick, hand-off per page) fitted from the goodput
plane's measured ``time_in_seconds{phase=}``
(:func:`ddl_tpu.obs.goodput.phase_cost_fit`).

Every control decision in the serve stack reads only that host half, so
a fleet on cost-model engines replays the **identical controller event
timeline and per-class shed/admit/requeue counts** as the real fleet —
the tick-for-tick parity pin in tests/test_twin.py. What the twin does
*not* reproduce is token VALUES (the hash is stable in ``(seed,
request_id, position)`` exactly like the real sampling key, so requeues
and preemptions replay the same stream) and wall-clock time (virtual
seconds accumulate in :meth:`CostModelEngine.virtual_time`, never in the
scheduler's ``perf_counter`` clock).  This is what lets 100–1000-replica
fleets replay million-request traces on a CPU box in seconds.
"""
from __future__ import annotations

import dataclasses
import types
from typing import Mapping

import numpy as np

from ..ops.kv_cache import PAD_POS
from .host import EngineHost

__all__ = ["CostModel", "CostModelEngine", "sim_engine_factory"]


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-phase virtual-time costs the twin charges.  The defaults are
    placeholder CPU-scale constants; fitted tables come from
    :func:`ddl_tpu.obs.goodput.phase_cost_fit` over a measured run's
    metrics (never hand-typed into experiments — the twin bench refuses
    silent drift by recording the fit alongside every sweep row)."""

    prefill_s_per_token: float = 1.2e-4
    decode_s_per_tick: float = 4.0e-3
    handoff_s_per_page: float = 3.0e-4

    @classmethod
    def from_phase_fit(cls, fit: Mapping[str, float]) -> "CostModel":
        """Build from a :func:`phase_cost_fit` table.  ``handoff`` is
        optional (a non-disagg run measures none); prefill/decode are
        required — a fit without them is not a serve run."""
        missing = [k for k in ("prefill_s_per_token", "decode_s_per_tick")
                   if k not in fit]
        if missing:
            raise ValueError(
                f"cost fit missing {', '.join(missing)} — fit it from a "
                "run that actually prefilled and decoded "
                "(obs.goodput.phase_cost_fit names the absent phase)"
            )
        return cls(
            prefill_s_per_token=float(fit["prefill_s_per_token"]),
            decode_s_per_tick=float(fit["decode_s_per_tick"]),
            handoff_s_per_page=float(
                fit.get("handoff_s_per_page",
                        cls.handoff_s_per_page)
            ),
        )


def _sim_token(seed: int, request_id: int, index: int, vocab: int) -> int:
    """Deterministic stand-in token: a 64-bit mix of ONLY
    ``(seed, request_id, position)`` — the same fold-in contract as the
    real sampler's PRNG key, so batch composition, slot assignment,
    requeue and preemption cannot change a request's stream.  Never 0
    (the pad id) so a token printout is visibly non-degenerate."""
    h = ((int(seed) & 0xFFFFFFFF) * 0x9E3779B1) & 0xFFFFFFFFFFFFFFFF
    h ^= ((int(request_id) & 0xFFFFFFFFFFFF) * 0x85EBCA77) \
        & 0xFFFFFFFFFFFFFFFF
    h ^= ((int(index) & 0xFFFFFFFF) * 0xC2B2AE3D) & 0xFFFFFFFFFFFFFFFF
    h ^= h >> 33
    h = (h * 0xFF51AFD7ED558CCD) & 0xFFFFFFFFFFFFFFFF
    h ^= h >> 33
    return 1 + h % max(vocab - 1, 1)


class _SimDevice:
    """The one 'device' a cost-model mesh exposes — enough surface for
    the memory sampler (which probes once, gets nothing, and latches
    off) and the peak-FLOPs lookup (platform ``cpu`` falls back to the
    CPU nominal without warning)."""

    platform = "cpu"
    device_kind = "sim-cost-model"
    id = 0

    def memory_stats(self):
        return None

    def __repr__(self):  # pragma: no cover - debugging nicety
        return "SimDevice(cost-model)"


class CostModelEngine(EngineHost):
    """No-array :class:`ServeEngine` (the module docstring): virtual time
    instead of device time, hashed tokens instead of a transformer.
    Accepts (and ignores) ``params``/``placed_params`` so the router's
    one-checkpoint replica wiring works unchanged."""

    kind = "sim"
    refuses = (
        (lambda c: c.speculate_k > 0,
         "speculate_k={c.speculate_k} has no cost-model implementation: "
         "draft acceptance depends on token CONTENT, which the twin does "
         "not model — run speculative configs on the real engine"),
    )

    def __init__(self, config, params=None, *, placed_params=None,
                 cost: CostModel | None = None):
        self._configure(config, params, placed_params)
        self.cost = cost if cost is not None else CostModel()
        self.params = placed_params  # opaque; replicas may share None
        # One fake CPU 'device' behind the same mesh surface the
        # observability plane reads (.devices.flat / .devices.size).
        self.mesh = types.SimpleNamespace(
            devices=np.array([_SimDevice()], dtype=object)
        )
        self.reset()

    # -- state -------------------------------------------------------------

    def reset(self) -> None:
        """Fresh empty state, same units as the real engine's reset:
        pool + tables + allocator + prefix index rebuilt together.  The
        virtual-time ledger resets too — warmup resets the engine before
        the timed run, so reported virtual seconds cover exactly the
        run, matching the wall-clock methodology."""
        self._reset_host()
        self.rows = np.zeros(self.config.slots, np.int64)  # for a dump's pos
        self.virtual = {"prefill": 0.0, "decode": 0.0, "handoff": 0.0}

    def virtual_time(self) -> dict:
        """Per-phase virtual seconds charged since the last reset, plus
        their sum under ``"total"`` — the twin's replacement for the
        wall clock when projecting policy costs."""
        out = dict(self.virtual)
        out["total"] = float(sum(self.virtual.values()))
        return out

    # -- the twin's own beside the shared host half: resident rows -----------

    def release_slot(self, slot: int) -> None:
        super().release_slot(slot)
        self.rows[slot] = 0

    def prefix_fetch(self, entry_id: int, n: int, slot: int) -> int:
        if self.paged:
            copied = super().prefix_fetch(entry_id, n, slot)
        else:  # no rows to copy: pin the entry, as a real engine does
            self._pin(entry_id)
            copied = n
        self.rows[slot] = n
        return copied

    def prefix_store(self, prompt, slot: int) -> bool:
        if self.paged:
            return super().prefix_store(prompt, slot)
        return self.prefix.insert(np.asarray(prompt, np.int32)) is not None

    # -- cross-replica hand-off --------------------------------------------

    def dump_slot_pages(self, slot: int):
        """Same ``(k, v, pos)`` contract as the real dump — ``pos`` is
        REAL (row positions in block-table order with the ``PAD_POS``
        tail; the coordinator counts pages and the loader counts rows
        from it); ``k``/``v`` are minimal placeholders whose page axis
        matches (``k.shape[1] == pos.shape[0]``, the shape invariant
        the preemption pin asserts).  Charges hand-off virtual time per
        page — one dump+load pair is one hand-off."""
        self._need_pages("dump_slot_pages")
        n = int(self.table_len[slot])
        ps = self.page_size
        rows = int(self.rows[slot])
        pos = np.full((n, ps), PAD_POS, np.int32)
        for i in range(n):
            filled = min(max(rows - i * ps, 0), ps)
            if filled:
                pos[i, :filled] = np.arange(i * ps, i * ps + filled,
                                            dtype=np.int32)
        k = np.zeros((1, n, ps, 1, 1), np.float32)
        v = np.zeros((1, n, ps, 1, 1), np.float32)
        self.virtual["handoff"] += n * self.cost.handoff_s_per_page
        return k, v, pos

    def load_slot_pages(self, slot: int, k, v, pos) -> list[int]:
        self._need_pages("load_slot_pages")
        n = int(k.shape[1])
        mapped = []
        for _ in range(n):
            mapped.append(self._map_page(slot))
        self.rows[slot] = int(np.count_nonzero(
            np.asarray(pos) != PAD_POS
        ))
        return mapped

    # -- host API ----------------------------------------------------------

    def prefill(self, prompt, *, slot: int, request_id: int, base: int = 0,
                _bucket: int | None = None, want_logits: bool = False):
        _, t, _ = self._prefill_block(prompt, base, _bucket)
        if self.paged:
            self._ensure_rows(slot, base + t)
        self.rows[slot] = max(int(self.rows[slot]), base + t)
        self.virtual["prefill"] += t * self.cost.prefill_s_per_token
        cfg = self.config
        nxt = _sim_token(cfg.seed, request_id, base + t, cfg.spec.vocab)
        return nxt, (np.zeros((t, cfg.spec.vocab), np.float32)
                     if want_logits else None)

    def decode(self, last_tokens, lengths, request_ids, active, *,
               _pages: int | None = None, want_logits: bool = False):
        cfg = self.config
        S = cfg.slots
        lengths_np = np.asarray(lengths, np.int64)
        active_np = np.asarray(active, bool)
        rids = np.asarray(request_ids, np.int64)
        if self.paged:
            self._decode_bucket(lengths_np, active_np, _pages)
        if _pages is None:
            # One batched step = one decode tick of virtual time; an
            # all-inactive warmup probe (_pages forced) charges nothing
            # and moves no state, like the real compile trigger.
            self.virtual["decode"] += self.cost.decode_s_per_tick
        nxt = np.zeros(S, np.int32)
        for s in np.nonzero(active_np)[0]:
            s = int(s)
            if _pages is None:
                self.rows[s] = max(int(self.rows[s]),
                                   int(lengths_np[s]) + 1)
            nxt[s] = _sim_token(cfg.seed, int(rids[s]),
                                int(lengths_np[s]) + 1, cfg.spec.vocab)
        return nxt, (np.zeros((S, cfg.spec.vocab), np.float32)
                     if want_logits else None)


def sim_engine_factory(cost: CostModel | None = None):
    """An ``engine_factory`` for :class:`~ddl_tpu.serve.router.RouterConfig`
    building cost-model engines that share one fitted :class:`CostModel`
    — the one-line switch that turns any fleet config into its digital
    twin."""

    def factory(config, params=None, *, placed_params=None):
        return CostModelEngine(config, params, placed_params=placed_params,
                               cost=cost)

    return factory
