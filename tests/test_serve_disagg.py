"""Disaggregated prefill/decode serving (ddl_tpu/serve/disagg.py,
ISSUE 15).

The acceptance chain: a seeded mixed-traffic stream served by a
1-prefill + 1-decode fleet emits tokens IDENTICAL (per (seed, id,
token_index)) to the same stream on a 2-replica mixed fleet, and the
per-step decode logits on the DESTINATION replica equal the colocated
run's bitwise at tp=1 AND tp=2 — the hand-off moves pages as bits
through the one compiled whole-page write program. Role grammar,
both-sides validation, per-role controller healing, the per-role
``/healthz`` digest and the analyze fleet-incident rendering ride
along.
"""

import dataclasses

import numpy as np
import pytest

from ddl_tpu.data.lm import synthesize_mixed_traffic
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry
from ddl_tpu.obs.analyze import build_report
from ddl_tpu.obs.goodput import SERVE_PHASES, fleet_summary
from ddl_tpu.obs.trace import FLEET_EVENTS
from ddl_tpu.resilience.faults import FaultInjector, FaultSpec
from ddl_tpu.serve import (
    AutoscaleConfig,
    ClassSpec,
    FleetController,
    RoleScale,
    Router,
    RouterConfig,
    ServeConfig,
    parse_autoscale_spec,
    parse_roles_spec,
    validate_roles,
)

SPEC = TINY_SPEC


def _traffic():
    return synthesize_mixed_traffic(
        classes={"chat": dict(rate=0.6, prompt_min=6, prompt_max=10,
                              max_new_tokens=4)},
        horizon=8, vocab=SPEC.vocab, seed=1, max_requests=6,
    )


def _record_decode_rows(router, rows):
    """Record every ACTIVE slot's decode logits row keyed by
    (request_id, lengths) across ALL the fleet's engines — placement
    and hand-off independent, so one recorder aligns a colocated run
    with a disaggregated one."""
    for eng in router.engines:
        d0 = eng.decode

        def dec(last, lengths, rids, act, *, _d0=d0, **kw):
            nxt, lg = _d0(last, lengths, rids, act, **kw,
                          want_logits=True)
            lg = np.asarray(lg)
            for s in range(len(act)):
                if act[s]:
                    rows[(int(rids[s]), int(lengths[s]))] = lg[s].copy()
            return nxt, lg

        eng.decode = dec


def test_parse_roles_spec_and_validation():
    """Grammar + the both-sides invariant: counts must sum to
    --replicas, a split fleet needs somewhere for arrivals to land AND
    somewhere for held prefixes to go, and every error names its
    offender."""
    assert parse_roles_spec("prefill=1,decode=2", 3) == \
        ("prefill", "decode", "decode")
    # Replica ids follow SEGMENT order — "decode=1,prefill=1" makes
    # replica 0 the decode specialist, exactly as written.
    assert parse_roles_spec("decode=1,prefill=1", 2) == \
        ("decode", "prefill")
    assert parse_roles_spec("mixed=2", 2) == ("mixed", "mixed")
    with pytest.raises(ValueError, match="sum to it"):
        parse_roles_spec("prefill=1,decode=1", 3)
    with pytest.raises(ValueError, match="unknown role"):
        parse_roles_spec("verify=1,decode=1", 2)
    with pytest.raises(ValueError, match="ROLE=COUNT"):
        parse_roles_spec("prefill", 1)
    with pytest.raises(ValueError, match="named twice"):
        parse_roles_spec("decode=1,decode=1", 2)
    with pytest.raises(ValueError, match="no prefill-capable"):
        parse_roles_spec("decode=2", 2)
    with pytest.raises(ValueError, match="no decode-"):
        parse_roles_spec("prefill=2", 2)
    # The symmetric starvation: decode replicas with only mixed peers
    # would never receive a hand-off (sources are prefill-only) nor an
    # arrival — dead capacity, rejected loudly.
    with pytest.raises(ValueError, match="idle forever"):
        parse_roles_spec("decode=1,mixed=1", 2)
    with pytest.raises(ValueError, match="no prefill-capable"):
        validate_roles(("decode",))
    # Router-side structural validation: length mismatch and the paged
    # requirement are ctor errors, never mid-run hangs.
    with pytest.raises(ValueError, match="one role per replica"):
        Router(RouterConfig(
            serve=ServeConfig(spec=SPEC, page_size=8, capacity=32),
            replicas=2, classes=(ClassSpec("chat"),),
            roles=("prefill",),
        ))
    with pytest.raises(ValueError, match="paged KV layout"):
        Router(RouterConfig(
            serve=ServeConfig(spec=SPEC),
            replicas=2, classes=(ClassSpec("chat"),),
            roles=("prefill", "decode"),
        ))


@pytest.mark.parametrize("tp", [1, 2])
def test_disagg_transparency_pin(tp):
    """THE disaggregation pin: same seeded stream, 1-prefill+1-decode
    fleet vs 2-replica mixed fleet — tokens identical per (seed, id,
    token_index), and every per-step decode logits row on the
    destination replica bitwise equals the colocated run's, tp=1 AND
    tp=2. Hand-offs are counted, traced, and leave both pools
    byte-whole."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12, tensor_parallel=tp)
    traffic = _traffic()
    classes = (ClassSpec("chat"),)
    rc = RouterConfig(serve=cfg, replicas=2, classes=classes)

    rows_m, rows_d = {}, {}
    r_mixed = Router(rc)
    _record_decode_rows(r_mixed, rows_m)
    done_m, _ = r_mixed.run(traffic)

    reg = MetricRegistry()
    r_dis = Router(dataclasses.replace(rc, roles=("prefill", "decode")),
                   registry=reg)
    _record_decode_rows(r_dis, rows_d)
    done_d, stats_d = r_dis.run(traffic)

    assert {i: done_d[i].tokens for i in done_d} == \
        {i: done_m[i].tokens for i in done_m}
    assert set(rows_m) == set(rows_d)
    for key, row in rows_m.items():
        np.testing.assert_array_equal(row, rows_d[key])
    # Every multi-token request crossed the fleet exactly once.
    multi = sum(1 for c in done_m.values() if len(c.tokens) > 1)
    assert stats_d.disagg["handoffs"] == multi
    assert int(reg.counter("handoff_total").value()) == multi
    assert int(reg.counter("handoff_pages_total").value()) \
        == stats_d.disagg["handoff_pages"] > 0
    names = [r["name"] for r in r_dis.tracer.records]
    assert "handoff" in names and "handoff" in FLEET_EVENTS
    # The decode work all happened on the decode replica: the prefill
    # replica's scheduler never ran a decode step.
    assert stats_d.replica[0].decode_steps == 0
    assert stats_d.replica[1].decode_steps > 0
    # Pools byte-whole on both sides after the run.
    for eng in r_dis.engines:
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0
    # The hand-off time was attributed: the source replica's goodput
    # phase vocabulary carries "handoff" (SERVE_PHASES grew it).
    assert "handoff" in SERVE_PHASES
    gp = r_dis.scheds[0].goodput
    assert gp is not None and gp.phases["handoff"] > 0.0


def test_disagg_tick_reproducible_and_role_digests():
    """Two fresh runs of the same seeded stream hand off at IDENTICAL
    ticks (deterministic host state only), and the role story is
    visible end-to-end: fleet_replicas_active{role=} gauges, the
    fleet_summary /healthz digest, and the analyze fleet-incident
    table's handoff rows with page counts."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12)
    traffic = _traffic()
    reg = MetricRegistry()
    router = Router(RouterConfig(serve=cfg, replicas=2,
                                 classes=(ClassSpec("chat"),),
                                 roles=("prefill", "decode")),
                    registry=reg)
    done_a, stats_a = router.run(traffic)
    events_a = list(router.disagg.events)
    router.reset()
    done_b, stats_b = router.run(traffic)
    assert events_a == router.disagg.events
    assert {i: done_a[i].tokens for i in done_a} == \
        {i: done_b[i].tokens for i in done_b}
    # Per-role gauges + the non-creating /healthz digest.
    g = reg.gauge("fleet_replicas_active")
    assert g.value(role="prefill") == 1 and g.value(role="decode") == 1
    digest = fleet_summary(reg)
    assert digest["replicas_by_role"] == {"prefill": 1, "decode": 1}
    assert digest["handoffs_total"] == stats_a.disagg["handoffs"] * 2
    # Analyze renders the handoff rows from the ONE shared
    # FLEET_EVENTS tuple, pages included.
    rep = build_report(
        [r for r in router.tracer.records]
    )
    hand = [f for f in rep["fleet_incidents"] if f["kind"] == "handoff"]
    assert hand and all(f["pages"] >= 1 and f["src"] == 0
                        and f["dst"] == 1 for f in hand)
    assert rep["incidents"]["handoff"] == len(hand)


def test_disagg_role_aware_crash_heal():
    """Role-aware healing: a crashed DECODE replica heals with a
    decode replica (not a mixed one — replacing the phase it killed),
    every request still completes exactly once with status ok, and the
    scale_out event names the role."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12)
    traffic = _traffic()
    inj = FaultInjector(FaultSpec(kind="replica_crash", step=4,
                                  replica=1))
    ctrl = FleetController(AutoscaleConfig(max_replicas=2,
                                           min_replicas=2),
                           injector=inj)
    router = Router(RouterConfig(serve=cfg, replicas=2,
                                 classes=(ClassSpec("chat"),),
                                 roles=("prefill", "decode")),
                    injector=inj, controller=ctrl)
    done, stats = router.run(traffic)
    assert ctrl.crashes == 1
    assert router.roles[2] == "decode"
    heal = [dict(e[2]) for e in ctrl.events if e[1] == "scale_out"]
    assert any(e.get("role") == "decode" and e.get("reason") == "heal"
               for e in heal)
    assert all(done[i].status == "ok" for i in done)
    assert stats.disagg["roles"] == {"prefill": 1, "decode": 1}


def _crashed_prefill_fleet():
    """A prefill=2,decode=1 fleet at fleet-wide min 3 with one prefill
    replica crashed mid-run — the finding-3 scenario: role floors alone
    (1 each) would leave the fleet at 2 < min_replicas forever. Helper
    holds the literals (the test_slo/_burst_arm budget pattern)."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12)
    inj = FaultInjector(FaultSpec(kind="replica_crash", step=3,
                                  replica=0))
    ctrl = FleetController(
        AutoscaleConfig(max_replicas=3, min_replicas=3, preempt=False),
        injector=inj,
    )
    router = Router(RouterConfig(serve=cfg, replicas=3,
                                 classes=(ClassSpec("chat"),),
                                 roles=("prefill", "prefill", "decode")),
                    injector=inj, controller=ctrl)
    done, stats = router.run(_traffic())
    return router, ctrl, done


def test_role_fleet_crash_heals_fleet_wide_minimum():
    """The fleet-wide floor holds on role fleets too: with per-role
    floors already satisfied (1 prefill + 1 decode live), a crash that
    drops the total below min_replicas still heals — topped up with
    the thinnest role — instead of sitting one replica short for the
    rest of the run (scale-in honors the min on the way down; crashes
    must not be the one path under it)."""
    router, ctrl, done = _crashed_prefill_fleet()
    assert ctrl.crashes == 1
    assert len(router.live_ids()) >= 3
    heal_roles = [dict(e[2]).get("role") for e in ctrl.events
                  if e[1] == "scale_out"
                  and dict(e[2]).get("reason") == "heal"]
    # Post-crash both roles sit at count 1 (floors satisfied); the
    # fleet-wide top-up breaks the tie deterministically — lowest
    # count first, then role name, so "decode" wins the 1-1 tie.
    assert heal_roles == ["decode"]
    assert all(done[i].status == "ok" for i in done)


def test_role_knobs_without_role_fleet_rejected_at_bind():
    """Finding-2 hardening: per-role autoscale knobs on an all-mixed
    fleet (or naming a role the fleet does not run) are bind-time
    config errors — the burn-rules discipline, not a silently-never-
    firing floor."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12)
    acfg = parse_autoscale_spec("decode.min=1", max_replicas=2)
    with pytest.raises(ValueError, match="need a disaggregated fleet"):
        Router(RouterConfig(serve=cfg, replicas=2,
                            classes=(ClassSpec("chat"),)),
               controller=FleetController(acfg))
    acfg2 = parse_autoscale_spec("mixed.min=1", max_replicas=2)
    with pytest.raises(ValueError, match="fleet does not run"):
        Router(RouterConfig(serve=cfg, replicas=2,
                            classes=(ClassSpec("chat"),),
                            roles=("prefill", "decode")),
               controller=FleetController(acfg2))


def test_per_role_autoscale_spec_parses_and_validates():
    """The ROLE.key=val grammar: per-role overrides land on RoleScale
    records, unknown roles/keys are named errors, and the config-level
    duplicate check fires."""
    acfg = parse_autoscale_spec(
        "backlog=3,prefill.backlog=2,decode.min=1,decode.max=2,"
        "prefill.sustain=1,decode.idle=4",
        max_replicas=4,
    )
    pf = acfg.role_scale("prefill")
    dc = acfg.role_scale("decode")
    assert pf.backlog_per_replica == 2.0 and pf.sustain_ticks == 1
    assert dc.min_replicas == 1 and dc.max_replicas == 2
    assert dc.idle_ticks == 4
    # Unset roles inherit all-default records.
    assert acfg.role_scale("mixed").backlog_per_replica is None
    with pytest.raises(ValueError, match="unknown role"):
        parse_autoscale_spec("verify.backlog=2", max_replicas=2)
    with pytest.raises(ValueError, match="per-role autoscale key"):
        parse_autoscale_spec("decode.burn=x", max_replicas=2)
    with pytest.raises(ValueError, match="must be > 0"):
        parse_autoscale_spec("decode.backlog=0", max_replicas=2)
    with pytest.raises(ValueError, match="duplicate role"):
        AutoscaleConfig(max_replicas=2,
                        roles=(RoleScale("decode"), RoleScale("decode")))
