"""Self-healing serve fleet (ddl_tpu/serve/controller.py, ISSUE 13).

The acceptance chain: a preempted-and-resumed request's tokens are
BIT-IDENTICAL to the same request served unpreempted — pinned via
per-step decode logits at tp=1 AND tp=2 (the KV hand-off moves pages as
bits; sampling keys fold in only (seed, request_id, token_index)); a
seeded ``replica_crash`` mid-decode heals with every in-flight request
completing exactly ONCE (status accounting pinned, tokens identical to
a crash-free run); and the seeded bulk-burst that fires the
``bulk_shed`` alert on a static fleet instead triggers scale-out — the
alert never fires, chat burn stays 0.0 through a full drain cycle, and
two fresh runs replay the controller's event timeline tick-identically.

Budget discipline: the burst arms live in a helper (the test_slo
pattern); the tier-1 tests stay within the tests/test_markers.py audit
bounds — ``max_replicas=`` literals now count into the topology budget
exactly like ``replicas=``.

The seeded bulk-burst and replica-crash specs themselves now live in
``ddl_tpu.serve.scenarios`` (ISSUE 18 dedupe): the pinned tests build
their runs from the SAME named scenarios the ``ddl_tpu sim`` CLI and
the twin bench replay, so the pins and the product scenario library
cannot drift.
"""

import json
import urllib.request

import numpy as np
import pytest

from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry, Tracer
from ddl_tpu.obs.export import MetricsExporter
from ddl_tpu.obs.goodput import fleet_summary
from ddl_tpu.obs.slo import SloMonitor
from ddl_tpu.resilience.faults import FaultInjector, FaultSpec, parse_fault
from ddl_tpu.serve import (
    AutoscaleConfig,
    ClassSpec,
    FleetController,
    InferenceEngine,
    Request,
    Router,
    RouterConfig,
    Scheduler,
    ServeConfig,
    parse_autoscale_spec,
)
from ddl_tpu.serve.scenarios import BULK_BURST, REPLICA_CRASH

SPEC = TINY_SPEC


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, SPEC.vocab, size=n, dtype=np.int32)


def _record_decodes(eng, log):
    d0 = eng.decode

    def dec(*a, **k):
        nxt, lg = d0(*a, **k, want_logits=True)
        log.append(np.asarray(lg).copy())
        return nxt, lg

    eng.decode = dec


@pytest.mark.parametrize("tp", [1, 2])
def test_preempt_resume_bit_identical(tp):
    """THE hand-off pin: a request preempted mid-decode (pages
    serialized host-side off scheduler A) and resumed on scheduler B
    produces the SAME tokens — and the SAME per-step decode logits,
    bitwise — as the oracle run that never moved, at tp=1 AND tp=2.
    Both pools read byte-whole (reservations included) afterwards."""
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=32, page_size=8,
                      num_pages=8, tensor_parallel=tp)
    req = Request(id=0, prompt=_prompt(6, 3), max_new_tokens=6)
    eng_o = InferenceEngine(cfg)
    logits_o = []
    _record_decodes(eng_o, logits_o)
    done_o, _ = Scheduler(eng_o).run([req])

    eng_a, eng_b = InferenceEngine(cfg), InferenceEngine(cfg)
    logits_ab = []
    _record_decodes(eng_a, logits_ab)
    _record_decodes(eng_b, logits_ab)
    tr = Tracer()
    sa, sb = Scheduler(eng_a, tracer=tr), Scheduler(eng_b, tracer=tr)
    sa.begin()
    sb.begin()
    sa.submit(req)
    for _ in range(3):
        sa.tick()
    pre = sa.preempt(0)
    assert len(pre.generated) == 4  # mid-decode: prefill tick made 2
    assert pre.k.shape[1] == pre.pos.shape[0]  # pages, table order
    sb.adopt(pre)
    while not sb.idle:
        sb.tick()
    done_a, _ = sa.collect()
    done_b, _ = sb.collect()
    sa.release()
    sb.release()
    # Completes exactly once, on the adopting scheduler.
    assert done_a == {} and done_b[0].status == "ok"
    assert done_b[0].tokens == done_o[0].tokens
    # Per-step decode logits: the full device-call sequence across the
    # move equals the oracle's, bitwise.
    assert len(logits_ab) == len(logits_o)
    for got, want in zip(logits_ab, logits_o):
        np.testing.assert_array_equal(got, want)
    # The preempt/resume lifecycle is in the trace, chained by req.
    names = [r["name"] for r in tr.records]
    assert names.index("preempt") < names.index("resume") \
        < names.index("complete")
    # Pools byte-whole: pages freed AND reservations cancelled.
    for eng in (eng_a, eng_b):
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0


def test_fleet_preemption_policy_bit_identical():
    """Full-stack preemption: a chat request queued behind a long bulk
    occupant (equal page reservations tie it to replica 0) is unblocked
    when the controller moves the bulk to the replica that freed up —
    chat admits EARLIER than the no-controller oracle, every token of
    every request is bit-identical, and the placement ledger shows the
    move."""
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=32, page_size=8,
                      num_pages=8)
    classes = (ClassSpec("chat", priority=0), ClassSpec("bulk", priority=2))
    reqs = [
        Request(id=0, prompt=_prompt(6, 0), max_new_tokens=16,
                arrival=0, traffic_class="bulk"),
        Request(id=1, prompt=_prompt(6, 1), max_new_tokens=12,
                arrival=0, traffic_class="bulk"),
        Request(id=2, prompt=_prompt(6, 2), max_new_tokens=2,
                arrival=2, traffic_class="chat"),
    ]
    router = Router(RouterConfig(serve=cfg, replicas=2, classes=classes))
    done_o, stats_o = router.run(reqs)
    assert stats_o.placements[2] == 0  # chat queued behind the long bulk

    ctrl = FleetController(AutoscaleConfig(max_replicas=2, min_replicas=2,
                                           preempt_wait_ticks=2))
    reg = MetricRegistry()
    router.registry = reg
    router.controller = ctrl
    ctrl.bind(router)
    router.reset()
    done_p, stats_p = router.run(reqs)
    assert ctrl.preemptions == 1
    assert int(reg.counter("preemptions_total").value()) == 1
    # The move is in the ledger: bulk 0 now lives on replica 1.
    assert stats_p.placements[0] == 1
    assert stats_p.fleet["preemptions"] == 1
    # Chat admitted strictly earlier than the oracle run.
    assert done_p[2].admitted_step < done_o[2].admitted_step
    # Every request's tokens bit-identical to the unpreempted run.
    assert {i: done_p[i].tokens for i in done_p} == \
        {i: done_o[i].tokens for i in done_o}
    assert all(done_p[i].status == "ok" for i in done_p)
    names = [r["name"] for r in router.tracer.records]
    assert "preempt" in names and "resume" in names \
        and "preempt_move" in names


def test_replica_crash_heals_and_completes_exactly_once():
    """THE crash pin: a seeded replica_crash mid-decode kills replica 1
    wholesale; its in-flight and queued requests requeue at the door
    (trace + counters), the fleet heals (min_replicas), and EVERY
    request completes exactly once with status "ok" and tokens
    identical to a crash-free run — the "requeued" placeholder is
    overwritten exactly once, router_requests_total counts each arrival
    once, and the crashed replica's stats slot reads None.

    The whole run — seeded requests, topology, fault schedule,
    autoscale policy — is built from the named REPLICA_CRASH scenario
    (serve.scenarios), the same definition the sim CLI and twin bench
    replay."""
    reqs = REPLICA_CRASH.build_traffic(SPEC.vocab)
    router = Router(REPLICA_CRASH.router_config(SPEC))
    done_o, stats_o = router.run(reqs)

    ctrl = REPLICA_CRASH.make_controller()
    reg = MetricRegistry()
    router.registry = reg
    router.controller = ctrl
    ctrl.bind(router)
    router.reset()
    done_c, stats_c = router.run(reqs)
    assert ctrl.crashes == 1 and ctrl.requeues >= 1
    crash = [r for r in router.tracer.records
             if r["name"] == "replica_crash"]
    assert len(crash) == 1 and crash[0]["attrs"]["replica"] == 1
    # Mid-decode: the crash caught at least one in-flight occupant.
    assert crash[0]["attrs"]["inflight"] >= 1
    assert [r["name"] for r in router.tracer.records].count("requeue") \
        == ctrl.requeues
    # Exactly-once accounting: every id present once, final status ok,
    # tokens identical to the crash-free oracle (sampling keys ignore
    # replicas and arrival), no "requeued" placeholder left behind.
    assert sorted(done_c) == sorted(done_o)
    for i in done_c:
        assert done_c[i].status == "ok", (i, done_c[i].status)
        assert done_c[i].tokens == done_o[i].tokens, i
    # Per-class tallies count each request once (no double count).
    assert sum(r.requests for r in stats_c.per_class.values()) == len(reqs)
    # SLO samples derive from each request's FINAL serve only: the
    # crashed attempt's token emissions are not folded in, so the
    # per-class ITL sample count equals the crash-free run's (same
    # tokens -> same gap count) instead of gaining duplicated prefix
    # samples plus a crash-spanning gap.
    assert stats_c.per_class["bulk"].itl.steps == \
        stats_o.per_class["bulk"].itl.steps
    # The live router_ttft_seconds histogram holds ONE sample per
    # request — a crash re-serve never observes a second TTFT.
    assert reg.histogram("router_ttft_seconds").count(
        **{"class": "bulk"}
    ) == len(reqs)
    assert int(reg.counter("router_requests_total").value(
        **{"class": "bulk"})) == len(reqs)
    assert int(reg.counter("fleet_crashes_total").value()) == 1
    # The crashed replica's device-side stats died with it; the healed
    # replacement (id 2) collected normally.
    assert stats_c.replica[1] is None
    assert stats_c.replica[0] is not None
    assert stats_c.fleet["crashes"] == 1

    # A crash tick beyond the run's horizon must FAIL loudly at run
    # end (a chaos run that exercised nothing must not pass clean).
    # ctrl's config is reused verbatim (only the injector differs) —
    # which also keeps the test inside the markers-audit cap ledger.
    late = FleetController(
        ctrl.config,
        injector=FaultInjector(FaultSpec(kind="replica_crash",
                                         step=999, replica=0)),
    )
    router.controller = late
    late.bind(router)
    router.reset()
    with pytest.raises(RuntimeError, match="never fired"):
        router.run(reqs[:1])


def _burst_arm(autoscale: bool):
    """The ISSUE 10 seeded bulk-burst scenario (test_slo._burst_run's
    traffic spec, verbatim — now the named BULK_BURST scenario in
    serve.scenarios) with the fleet controller as the only delta: the
    static arm sheds and alerts; the autoscale arm scales out instead.
    Returns (monitor, controller, router stats, done, tracer)."""
    traffic = BULK_BURST.build_traffic(SPEC.vocab)
    reg, tr = MetricRegistry(), Tracer()
    mon = SloMonitor(BULK_BURST.slo_rules(), reg, tracer=tr)
    ctrl = BULK_BURST.make_controller() if autoscale else None
    router = Router(BULK_BURST.router_config(SPEC), registry=reg,
                    tracer=tr, slo_monitor=mon, controller=ctrl)
    done, rstats = router.run(traffic)
    return mon, ctrl, rstats, done, tr


def test_burst_scale_out_instead_of_shed_tick_reproducible():
    """THE scenario pin (ISSUE 13 satellite): the same seeded traffic
    spec that fires the bulk_shed alert on the static fleet instead
    triggers SCALE-OUT — the alert never fires, the door sheds nothing
    (the deferral), total bulk sheds drop, chat burn stays 0.0 through
    a FULL drain cycle (scale_out -> drain -> scale_in all happen), and
    two fresh runs replay the controller's event timeline and every
    token tick-identically."""
    s_mon, _, s_stats, _, _ = _burst_arm(autoscale=False)
    assert s_mon.alerts("bulk_shed") >= 1  # the static arm DOES alert
    assert s_stats.per_class["bulk"].shed > 0

    mon, ctrl, rstats, done, tr = _burst_arm(autoscale=True)
    assert ctrl.scale_outs >= 1 and ctrl.drains >= 1 \
        and ctrl.scale_ins >= 1  # the full cycle
    assert mon.alerts("bulk_shed") == 0  # scale-out replaced the alert
    # The door deferred while the fleet could grow; at max scale it is
    # the backstop again — strictly fewer door sheds AND fewer total
    # bulk sheds than the static arm.
    assert rstats.router_sheds < s_stats.router_sheds
    assert rstats.per_class["bulk"].shed < s_stats.per_class["bulk"].shed
    # Chat stayed green the whole run.
    assert mon.alerts("chat_shed") == 0
    assert mon.burn_rate("chat_shed", "fast") == 0.0
    assert mon.burn_rate("chat_shed", "slow") == 0.0
    assert rstats.per_class["chat"].shed == 0
    kinds = [r["name"] for r in tr.records
             if r["name"] in ("scale_out", "drain", "scale_in")]
    assert kinds and kinds[0] == "scale_out"

    mon2, ctrl2, rstats2, done2, _ = _burst_arm(autoscale=True)
    assert ctrl2.events == ctrl.events  # tick-identical timeline
    assert {i: done2[i].tokens for i in done2} == \
        {i: done[i].tokens for i in done}
    assert {i: done2[i].status for i in done2} == \
        {i: done[i].status for i in done}
    for name in ("bulk_shed", "chat_shed"):
        assert mon2.cumulative(name) == mon.cumulative(name)


def test_drain_stops_routing_then_removes():
    """Drain semantics: once a replica begins draining it receives NO
    routed arrivals (placement skips it) while its occupants finish;
    only then is it collected and removed — its ServeStats survive in
    the stats list and later arrivals all land on the survivor."""
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=32, page_size=8,
                      num_pages=8)
    classes = (ClassSpec("bulk", priority=1),)
    # Two early co-arrivals spread over both replicas; replica 1 then
    # idles past idle_ticks while late arrivals keep replica 0 busy.
    reqs = [
        Request(id=0, prompt=_prompt(6, 20), max_new_tokens=12,
                arrival=0, traffic_class="bulk"),
        Request(id=1, prompt=_prompt(6, 21), max_new_tokens=2,
                arrival=0, traffic_class="bulk"),
        Request(id=2, prompt=_prompt(6, 22), max_new_tokens=2,
                arrival=8, traffic_class="bulk"),
    ]
    ctrl = FleetController(AutoscaleConfig(max_replicas=2, min_replicas=1,
                                           idle_ticks=3, preempt=False,
                                           backlog_per_replica=10.0))
    router = Router(RouterConfig(serve=cfg, replicas=2, classes=classes),
                    controller=ctrl)
    done, stats = router.run(reqs)
    assert all(done[i].status == "ok" for i in done)
    drains = [r for r in router.tracer.records if r["name"] == "drain"]
    assert drains, "replica 1 should have drained mid-run"
    drain_tick = drains[0]["attrs"]["tick"]
    assert drains[0]["attrs"]["replica"] == 1
    # No arrival routed to the draining replica after the drain began.
    for r in router.tracer.records:
        if r["name"] == "route" and r["attrs"]["tick"] >= drain_tick:
            assert r["attrs"]["replica"] != 1
    # Removed from the fleet, stats collected, not crashed.
    assert router.scheds[1] is None
    assert stats.replica[1] is not None
    assert ctrl.scale_ins >= 1


def test_autoscale_spec_and_validation():
    """Loud-config discipline: the --autoscale grammar round-trips, bad
    keys/values and invalid configs are named errors, and a controller
    refuses to bind a router already above its cap."""
    acfg = parse_autoscale_spec(
        "max=4,min=2,backlog=3.5,sustain=3,idle=6,preempt=0,wait=4,"
        "gap=2,burn=bulk_shed|chat_shed,defer=0"
    )
    assert acfg.max_replicas == 4 and acfg.min_replicas == 2
    assert acfg.backlog_per_replica == 3.5 and acfg.sustain_ticks == 3
    assert acfg.idle_ticks == 6 and acfg.preempt is False
    assert acfg.preempt_wait_ticks == 4 and acfg.preempt_priority_gap == 2
    assert acfg.burn_rules == ("bulk_shed", "chat_shed")
    assert acfg.defer_door_shed is False  # the conservative opt-out
    # --max-replicas overrides the spec's max; min defaults to the
    # seed replica count capped at max.
    over = parse_autoscale_spec("max=4", max_replicas=2, replicas=3)
    assert over.max_replicas == 2 and over.min_replicas == 2
    with pytest.raises(ValueError, match="fleet cap"):
        parse_autoscale_spec("backlog=2")
    with pytest.raises(ValueError, match="unknown autoscale key"):
        parse_autoscale_spec("max=2,frob=1")
    with pytest.raises(ValueError, match="bad value"):
        parse_autoscale_spec("max=two")
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscaleConfig(max_replicas=1, min_replicas=2)
    with pytest.raises(ValueError, match="backlog_per_replica"):
        AutoscaleConfig(max_replicas=2, backlog_per_replica=0)
    with pytest.raises(ValueError, match="sustain_ticks"):
        AutoscaleConfig(max_replicas=2, sustain_ticks=0)
    with pytest.raises(ValueError, match="above max_replicas"):
        Router(RouterConfig(serve=ServeConfig(spec=SPEC, slots=1,
                                              capacity=16),
                            replicas=2,
                            classes=(ClassSpec("chat"),)),
               controller=FleetController(AutoscaleConfig(max_replicas=1)))
    assert parse_fault("replica_crash@7:2") == FaultSpec(
        kind="replica_crash", step=7, replica=2
    )
    with pytest.raises(ValueError, match="replica_crash takes"):
        parse_fault("replica_crash@x:y")
    with pytest.raises(ValueError, match="replica"):
        FaultSpec(kind="replica_crash", step=1, replica=-1)


def test_healthz_fleet_digest_and_summary():
    """ISSUE 13 satellite: /healthz carries the fleet digest (replicas
    active/draining, last scale tick, preemptions) via the non-creating
    MetricRegistry.get pattern — present when the controller published,
    absent on a fleet-less registry, and reading creates nothing."""
    reg = MetricRegistry()
    assert fleet_summary(reg) == {}
    assert not [m.name for m in reg.metrics()]  # get created nothing
    reg.gauge("fleet_replicas_active").set(3)
    reg.gauge("fleet_replicas_draining").set(1)
    reg.gauge("fleet_last_scale_tick").set(17)
    reg.counter("preemptions_total").inc(2)
    digest = fleet_summary(reg)
    assert digest == {"replicas_active": 3, "replicas_draining": 1,
                      "last_scale_tick": 17, "preemptions_total": 2}
    with MetricsExporter(reg, 0) as exp:
        health = json.loads(urllib.request.urlopen(
            exp.url("/healthz")
        ).read())
    assert health["status"] == "ok"
    for key, want in digest.items():
        assert health[key] == want


def test_fleet_incident_report_and_chrome_flows():
    """ISSUE 13 satellite: the analyze report renders the fleet-incident
    table from the trace, and the Chrome converter emits the fleet
    events under cat=incident with a preempt -> resume -> complete flow
    chain (keyed by req) and a drain -> scale_in chain (keyed by
    replica)."""
    from ddl_tpu.obs.analyze import build_report
    from ddl_tpu.obs.trace import chrome_trace_events

    tr = Tracer()
    tr.event("scale_out", tick=3, replica=1, reason="pressure")
    tr.event("preempt", req=7, slot=0, step=5, tokens=3)
    tr.event("resume", req=7, slot=0, step=2, tokens=3)
    tr.event("complete", req=7, slot=0, step=9, tokens=6, status="ok")
    tr.event("drain", tick=11, replica=1)
    tr.event("scale_in", tick=12, replica=1)
    rep = build_report(tr.records)
    kinds = [f["kind"] for f in rep["fleet_incidents"]]
    assert kinds == ["scale_out", "preempt", "resume", "drain", "scale_in"]
    assert rep["fleet_incidents"][0] == {"kind": "scale_out", "tick": 3,
                                         "replica": 1,
                                         "reason": "pressure"}
    assert rep["incidents"]["preempt"] == 1
    assert rep["incidents"]["scale_in"] == 1

    events = chrome_trace_events(tr.records)
    incidents = [e for e in events if e.get("cat") == "incident"]
    assert {e["name"] for e in incidents} == {
        "scale_out", "preempt", "resume", "drain", "scale_in"
    }
    assert all(e["s"] == "g" for e in incidents)
    flows = [e for e in events if e.get("cat") == "incident_flow"]
    req_chain = [e for e in flows if e["name"] == "incident:req=7"]
    # s (preempt) -> t (resume) -> f (complete): the hand-off rendered.
    assert [e["ph"] for e in req_chain] == ["s", "t", "f"]
    rep_chain = [e for e in flows if e["name"] == "incident:replica=1"]
    assert [e["ph"] for e in rep_chain] == ["s", "t", "f"]
