"""The million-request digital twin (serve.sim + serve.scenarios,
ISSUE 18).

THE parity pin: the cost-model engine replays the two pinned CI
scenarios — bulk_burst and replica_crash — TICK-FOR-TICK against the
real fleet: identical controller event timelines, identical per-class
request/shed tallies, identical per-request admission ticks and final
statuses, across two fresh sim runs AND against the real engine.  The
twin's only deltas are the token VALUES (hashed, not sampled) and the
clock (virtual, not wall) — every control-plane decision is the real
one, because the sim runs the real scheduler/router/controller code
against mirrored host bookkeeping.

Transparency: a twin run is always LABELLED — ``fleet_engine_sim`` in
the registry, ``engine_kind`` in the fleet digest and ``/healthz`` —
and renders through the SAME obs.analyze incident table as a real run.

Scale: the slow-marked smoke replays a 1,000,000-request diurnal trace
over a 128-replica sim fleet on CPU inside the CI wall budget — the
policy-search envelope no real CPU fleet could touch.
"""

import dataclasses
import json
import urllib.request

import numpy as np
import pytest

from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry, Tracer
from ddl_tpu.obs.export import MetricsExporter
from ddl_tpu.obs.goodput import fleet_summary, phase_cost_fit
from ddl_tpu.obs.slo import SloMonitor
from ddl_tpu.obs.trace import NULL_TRACER
from ddl_tpu.resilience.faults import (
    FaultSpec,
    FaultStorm,
    parse_fault_storm,
)
from ddl_tpu.serve import (
    AutoscaleConfig,
    FleetController,
    HybridEngine,
    InferenceEngine,
    Request,
    Router,
    Scheduler,
    ServeConfig,
)
from ddl_tpu.serve.engine_iface import ServeEngine, engine_kind
from ddl_tpu.serve.scenarios import (
    BULK_BURST,
    DIURNAL,
    REPLICA_CRASH,
    SCENARIOS,
    get_scenario,
    parse_scenario,
)
from ddl_tpu.serve.sim import CostModel, CostModelEngine, sim_engine_factory

SPEC = TINY_SPEC


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, SPEC.vocab, size=n, dtype=np.int32)


def _arm(scn, *, sim):
    """One fleet run of scenario ``scn`` — real engines or the
    cost-model twin, everything else identical (the parity harness)."""
    factory = sim_engine_factory() if sim else None
    traffic = scn.build_traffic(SPEC.vocab)
    reg, tr = MetricRegistry(), Tracer()
    mon = None
    if scn.slo_rule_classes:
        mon = SloMonitor(scn.slo_rules(), reg, tracer=tr)
    ctrl = scn.make_controller()
    router = Router(scn.router_config(SPEC, engine_factory=factory),
                    registry=reg, tracer=tr, slo_monitor=mon,
                    controller=ctrl)
    done, stats = router.run(traffic)
    return done, stats, ctrl, mon, reg, tr


def _assert_tick_parity(real, sim):
    """Controller timeline + per-request admission/status + per-class
    tallies identical between two arms."""
    done_a, stats_a, ctrl_a = real[0], real[1], real[2]
    done_b, stats_b, ctrl_b = sim[0], sim[1], sim[2]
    assert ctrl_b.events == ctrl_a.events
    assert sorted(done_b) == sorted(done_a)
    assert {i: done_b[i].status for i in done_b} == \
        {i: done_a[i].status for i in done_a}
    assert {i: done_b[i].admitted_step for i in done_b} == \
        {i: done_a[i].admitted_step for i in done_a}
    for c in stats_a.per_class:
        a, b = stats_a.per_class[c], stats_b.per_class[c]
        assert (b.requests, b.shed) == (a.requests, a.shed), c
    assert stats_b.router_sheds == stats_a.router_sheds


def test_bulk_burst_twin_parity_tick_for_tick():
    """THE parity pin, scenario 1: the autoscaled bulk-burst run —
    scale_out/drain/scale_in timeline, every admission tick, every
    shed, the SLO burn ledger — replays identically on the cost-model
    twin, across two fresh twin runs, and each arm self-labels its
    engine kind in the fleet digest."""
    real = _arm(BULK_BURST, sim=False)
    sim1 = _arm(BULK_BURST, sim=True)
    sim2 = _arm(BULK_BURST, sim=True)
    _assert_tick_parity(real, sim1)
    _assert_tick_parity(sim1, sim2)
    assert real[2].scale_outs >= 1  # the scenario actually scaled
    for name in ("bulk_shed", "chat_shed"):
        assert sim1[3].cumulative(name) == real[3].cumulative(name)
    assert fleet_summary(sim1[4])["engine_kind"] == "sim"
    assert fleet_summary(real[4])["engine_kind"] == "real"


def test_replica_crash_twin_parity_tick_for_tick():
    """THE parity pin, scenario 2: the seeded replica crash — crash
    tick, requeue count, heal, exactly-once completion — replays
    identically on the twin; the crashed replica's stats slot reads
    None in both arms and the crash counters agree."""
    real = _arm(REPLICA_CRASH, sim=False)
    sim1 = _arm(REPLICA_CRASH, sim=True)
    sim2 = _arm(REPLICA_CRASH, sim=True)
    _assert_tick_parity(real, sim1)
    _assert_tick_parity(sim1, sim2)
    for arm in (real, sim1, sim2):
        done, stats, ctrl = arm[0], arm[1], arm[2]
        assert ctrl.crashes == 1
        assert all(done[i].status == "ok" for i in done)
        assert stats.replica[1] is None
        assert stats.fleet["crashes"] == 1
    assert sim1[2].requeues == real[2].requeues
    crash_a = [r for r in real[5].records if r["name"] == "replica_crash"]
    crash_b = [r for r in sim1[5].records if r["name"] == "replica_crash"]
    assert len(crash_a) == len(crash_b) == 1
    assert crash_a[0]["attrs"]["replica"] == crash_b[0]["attrs"]["replica"]


def test_twin_run_renders_through_analyze_report():
    """Transparency: a twin run's trace renders through the SAME
    obs.analyze fleet-incident table as a real run — no special-cased
    sim path, same FLEET_EVENTS kinds."""
    from ddl_tpu.obs.analyze import build_report

    arm = _arm(BULK_BURST, sim=True)
    rep = build_report(arm[5].records)
    kinds = [f["kind"] for f in rep["fleet_incidents"]]
    assert "scale_out" in kinds and "drain" in kinds
    assert rep["incidents"]["scale_out"] >= 1


# Every attribute and method the scheduler, the router, the controller,
# the disagg coordinator and cli.py read of an engine (the scheduler SETS
# ledger_hook and reads it nowhere).
_ENGINE_SURFACE = (
    "kind", "handoff", "config", "mesh", "params", "compile_hook",
    "last_attend_width", "last_counters",
    "paged", "page_size", "max_pages", "num_pages",
    "pages", "tables", "table_len", "reserved_for", "page_copies", "prefix",
    "prefill", "decode", "prefill_bucket", "decode_page_bucket", "reset",
    "pages_needed", "reserve_pages", "can_admit", "reclaim_pages",
    "release_slot", "dump_slot_pages", "load_slot_pages",
    "alias_slot_pages", "handoff_bytes",
    "prefix_fetch", "prefix_release", "prefix_store",
)


@pytest.mark.parametrize("family", ["sim", "dense", "hybrid"])
def test_sim_engine_satisfies_serve_engine_protocol(family):
    """The control-plane contract: every engine satisfies the
    runtime-checkable ServeEngine protocol, carries every name the
    control plane reads (a method given to one engine and not to the
    twin, as ``handoff_bytes`` was until PR 30, fails here and not in a
    crash replay), takes its host half from the one ``EngineHost``
    rather than a copy, and self-reports its kind (the twin can never
    masquerade — engine_kind defaults to real only for engines
    predating the interface)."""
    from ddl_tpu.models.hybrid import HybridSpec

    cls, spec = {"sim": (CostModelEngine, SPEC),
                 "dense": (InferenceEngine, SPEC),
                 "hybrid": (HybridEngine, HybridSpec())}[family]
    eng = cls(ServeConfig(spec=spec, slots=1, capacity=32, page_size=8,
                          num_pages=8))
    assert isinstance(eng, ServeEngine)
    assert [n for n in _ENGINE_SURFACE if not hasattr(eng, n)] == []
    assert engine_kind(eng) == ("sim" if family == "sim" else "real")
    assert engine_kind(object()) == "real"  # pre-interface default
    # Written once: the bookkeeping is EngineHost's own function in every
    # class; an engine overrides only to add state of its own around it.
    from ddl_tpu.serve.host import EngineHost

    for name in ("pages_needed", "reclaim_pages", "_map_page",
                 "_ensure_rows", "_release_pages", "prefill_bucket",
                 "decode_page_bucket", "handoff_bytes", "prefix_release"):
        assert getattr(cls, name) is getattr(EngineHost, name), name
    assert eng.handoff_bytes(2) > 0


def test_sim_engine_scheduler_roundtrip_and_virtual_time():
    """The cost-model engine drives the REAL scheduler end to end
    (paged admission, warmup ladder, prefix pool) — deterministic
    hashed tokens across two fresh engines, a monotone virtual-time
    ledger per phase, pools byte-whole after release."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12, prefix_slots=4)
    reqs = [Request(id=i, prompt=_prompt(6, 30 + i), max_new_tokens=4)
            for i in range(3)]
    eng = CostModelEngine(cfg)
    sched = Scheduler(eng)
    sched.warmup(reqs)  # the real warmup ladder, no compiles
    done, stats = sched.run(reqs)
    assert sorted(done) == [0, 1, 2]
    assert all(done[i].status == "ok" for i in done)
    assert all(len(done[i].tokens) == 4 for i in done)
    vt = eng.virtual_time()
    assert vt["prefill"] > 0 and vt["decode"] > 0
    assert vt["total"] == pytest.approx(
        vt["prefill"] + vt["decode"] + vt["handoff"])
    assert eng.pages.free == eng.num_pages and eng.pages.reserved == 0
    done2, _ = Scheduler(CostModelEngine(cfg)).run(reqs)
    assert {i: done2[i].tokens for i in done2} == \
        {i: done[i].tokens for i in done}


def test_sim_engine_preempt_adopt_bit_identical():
    """The twin mirrors the page hand-off: a request preempted off sim
    scheduler A and adopted on sim B emits the SAME hashed tokens as
    the unpreempted sim oracle (sampling state is (seed, request_id,
    token_index) in both worlds), the hand-off charges virtual
    hand-off time, and both pools read byte-whole."""
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=32, page_size=8,
                      num_pages=8)
    req = Request(id=0, prompt=_prompt(6, 3), max_new_tokens=6)
    done_o, _ = Scheduler(CostModelEngine(cfg)).run([req])

    eng_a, eng_b = CostModelEngine(cfg), CostModelEngine(cfg)
    sa, sb = Scheduler(eng_a), Scheduler(eng_b)
    sa.begin()
    sb.begin()
    sa.submit(req)
    for _ in range(3):
        sa.tick()
    pre = sa.preempt(0)
    assert pre.k.shape[1] == pre.pos.shape[0]  # pages, table order
    sb.adopt(pre)
    while not sb.idle:
        sb.tick()
    done_a, _ = sa.collect()
    done_b, _ = sb.collect()
    sa.release()
    sb.release()
    assert done_a == {} and done_b[0].status == "ok"
    assert done_b[0].tokens == done_o[0].tokens
    assert eng_a.virtual_time()["handoff"] > 0  # the dump was charged
    for eng in (eng_a, eng_b):
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0


def test_sim_engine_rejects_speculation():
    """Loud-config: speculative decoding has no cost model (draft
    acceptance depends on token VALUES, which the twin hashes) — a
    speculate_k config is a named error, not silently-wrong numbers."""
    with pytest.raises(ValueError, match="cost-model"):
        CostModelEngine(ServeConfig(spec=SPEC, slots=2, capacity=32,
                                    page_size=8, num_pages=16,
                                    speculate_k=2))


def test_cost_model_fit_roundtrip_and_loud_errors():
    """phase_cost_fit: per-phase costs from a live registry and from a
    metrics JSONL agree exactly (last snapshot wins); a phase the run
    never attributed is a loud error naming it; the fitted dict feeds
    CostModel.from_phase_fit, which requires both serve phases."""
    reg = MetricRegistry()
    reg.gauge("time_in_seconds").set(1.2, phase="prefill")
    reg.gauge("time_in_seconds").set(0.8, phase="decode")
    reg.counter("serve_prefill_tokens_total").inc(1000)
    for _ in range(200):
        reg.histogram("serve_decode_step_seconds").observe(0.004)
    fit = phase_cost_fit(reg)
    assert fit["prefill_s_per_token"] == pytest.approx(0.0012)
    assert fit["decode_s_per_tick"] == pytest.approx(0.004)
    with pytest.raises(ValueError, match="handoff"):
        phase_cost_fit(reg, phases=("prefill", "decode", "handoff"))
    with pytest.raises(ValueError, match="unknown fit phase"):
        phase_cost_fit(reg, phases=("warp",))
    cm = CostModel.from_phase_fit(fit)
    assert cm.prefill_s_per_token == pytest.approx(0.0012)
    with pytest.raises(ValueError, match="decode_s_per_tick"):
        CostModel.from_phase_fit({"prefill_s_per_token": 1e-4})


def test_phase_cost_fit_from_metrics_jsonl(tmp_path):
    """The offline path: the fit reads the LAST snapshot of a
    MetricsWriter JSONL (costs are cumulative ratios) and matches the
    live-registry fit bit for bit; a snapshot-less file is loud."""
    reg = MetricRegistry()
    reg.gauge("time_in_seconds").set(0.6, phase="prefill")
    reg.gauge("time_in_seconds").set(0.4, phase="decode")
    reg.counter("serve_prefill_tokens_total").inc(500)
    for _ in range(100):
        reg.histogram("serve_decode_step_seconds").observe(0.004)
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"record": "manifest", "run": "x"}) + "\n")
        f.write(json.dumps({"record": "snapshot", "metrics": [
            {"name": "time_in_seconds", "kind": "gauge",
             "labels": {"phase": "prefill"}, "value": 99.0},
        ]}) + "\n")
        f.write(json.dumps({"record": "snapshot",
                            "metrics": reg.snapshot()}) + "\n")
    assert phase_cost_fit(str(path)) == phase_cost_fit(reg)
    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps({"record": "manifest"}) + "\n")
    with pytest.raises(ValueError, match="no snapshot"):
        phase_cost_fit(str(empty))


def test_healthz_carries_engine_kind():
    """/healthz transparency: the fleet digest (and thus the health
    endpoint) labels the engine kind via the non-creating registry
    read — absent on a registry no router ever stamped."""
    reg = MetricRegistry()
    assert "engine_kind" not in fleet_summary(reg)
    assert not [m.name for m in reg.metrics()]  # read created nothing
    reg.gauge("fleet_engine_sim").set(1.0)
    reg.gauge("fleet_replicas_active").set(2)
    assert fleet_summary(reg)["engine_kind"] == "sim"
    with MetricsExporter(reg, 0) as exp:
        health = json.loads(urllib.request.urlopen(
            exp.url("/healthz")
        ).read())
    assert health["engine_kind"] == "sim"
    reg.gauge("fleet_engine_sim").set(0.0)
    assert fleet_summary(reg)["engine_kind"] == "real"


def test_scenario_library_grammar_and_validation():
    """The scenario surface: every named scenario parses, overrides
    apply (and are rejected on pinned-request scenarios), unknown
    names/keys are loud, and the fault-storm grammar sequences
    multi-crash schedules one per tick."""
    assert set(SCENARIOS) == {"bulk_burst", "replica_crash", "diurnal",
                              "crash_storm", "role_mix",
                              "longtail_prefix"}
    scn, over = parse_scenario("diurnal:horizon=128,rate_scale=2.5")
    assert scn.name == "diurnal"
    assert over == {"horizon": 128, "rate_scale": 2.5}
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("rush_hour")
    with pytest.raises(ValueError, match="bad scenario override"):
        parse_scenario("diurnal:frobs=2")
    with pytest.raises(ValueError, match="pins an explicit request"):
        REPLICA_CRASH.build_traffic(SPEC.vocab, rate_scale=2.0)
    # The pinned request list is the test_fleet recipe, verbatim.
    reqs = REPLICA_CRASH.build_traffic(SPEC.vocab)
    assert [r.arrival for r in reqs] == [0, 0, 1, 1]
    np.testing.assert_array_equal(reqs[0].prompt, _prompt(6, 10))

    storm = parse_fault_storm("replica_crash@3:1;replica_crash@3:2")
    assert isinstance(storm, FaultStorm)
    assert storm.crashes_replica(3) == 1  # one per tick, step order
    assert storm.crashes_replica(3) == 2
    assert storm.crashes_replica(4) is None
    assert not storm.crash_pending
    storm.rearm()
    assert storm.crash_pending and storm.spec.step == 3
    with pytest.raises(ValueError, match="replica_crash faults only"):
        FaultStorm((FaultSpec(kind="stall", step=1),))


@pytest.mark.slow
def test_million_request_twin_scale_smoke():
    """THE scale pin: a 1,000,000-request diurnal trace over a
    128-replica cost-model fleet completes on CPU inside the CI wall
    budget (the twin-parity job's bound) — every request reaches a
    terminal decision, the overwhelming majority serve clean, and the
    per-class ledgers account for every arrival exactly once. No
    registry, no kept trace: the pure control-plane envelope."""
    import time

    scn = dataclasses.replace(DIURNAL, slots=8, capacity=64,
                              shed_threshold=16)
    t0 = time.perf_counter()
    traffic = scn.build_traffic(SPEC.vocab, horizon=3000,
                                rate_scale=425.0, max_requests=1_000_000)
    assert len(traffic) == 1_000_000
    ctrl = FleetController(AutoscaleConfig(
        max_replicas=128, min_replicas=128, preempt=False,
        backlog_per_replica=1e9))
    router = Router(
        scn.router_config(SPEC, replicas=128,
                          engine_factory=sim_engine_factory()),
        tracer=NULL_TRACER, controller=ctrl)
    done, stats = router.run(traffic)
    wall = time.perf_counter() - t0
    assert wall < 570.0, f"1M-request twin run took {wall:.0f}s"
    assert len(done) == 1_000_000
    ok = sum(1 for d in done.values() if d.status == "ok")
    assert ok >= 900_000  # the fleet actually served, not shed, the load
    assert sum(s.requests for s in stats.per_class.values()) == 1_000_000
