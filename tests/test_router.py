"""Multi-tenant router (ddl_tpu/serve/router.py, ISSUE 8).

The acceptance chain: a 1-replica router run is BIT-IDENTICAL (tokens
AND per-device-call logits) to driving the bare ``Scheduler`` on the
same request stream — the router adds policy, never numerics; an
N=2-replica mixed-traffic run is seed-deterministic (tokens and routing
decisions replay exactly); and under a seeded burst, prefix affinity
measurably lifts the chat-class hit rate while BULK (not chat) absorbs
the overload as router sheds — all pinned via trace events, registry
counters and the ``RouterStats``/``ServeStats`` product surfaces, never
private scheduler state.

Budget discipline: the wide burst A/B (two 2-replica routers = four
compiled engines) is ``slow``; the tier-1 pins stay within the
tests/test_markers.py audit bounds (<= 64 est. tokens, <= 2 replicas).
"""

import dataclasses

import numpy as np
import pytest

from ddl_tpu.data.lm import synthesize_mixed_traffic, synthesize_prompts
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry
from ddl_tpu.serve import (
    ClassSpec,
    InferenceEngine,
    Request,
    Router,
    RouterConfig,
    Scheduler,
    ServeConfig,
    parse_slo_spec,
    parse_traffic_spec,
)

SPEC = TINY_SPEC


def _record_device_calls(eng, log):
    """Wrap an engine's prefill/decode so every device call's logits
    land in ``log`` — the bit-identity pin compares the full call
    sequence, not just final tokens."""
    d0, p0 = eng.decode, eng.prefill

    def dec(*a, **k):
        nxt, lg = d0(*a, **k, want_logits=True)
        log.append(("decode", np.asarray(lg).copy()))
        return nxt, lg

    def pre(*a, **k):
        nxt, lg = p0(*a, **k, want_logits=True)
        log.append(("prefill", np.asarray(lg).copy()))
        return nxt, lg

    eng.decode, eng.prefill = dec, pre


@pytest.mark.parametrize("tp", [1, 2])
def test_router_single_replica_bit_identical_to_bare_scheduler(tp):
    """THE transparency pin: one replica behind the router ≡ the bare
    Scheduler on the same staggered stream — same tokens, same
    admitted steps, and the SAME device-call sequence with bitwise-
    equal logits (idle router ticks make no device calls), at tp=1 AND
    tp=2."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, tensor_parallel=tp)
    prompts = synthesize_prompts(num=5, min_len=3, max_len=9,
                                 vocab=SPEC.vocab, seed=6)
    arrivals = [0, 0, 1, 3, 7]  # co-arrivals AND an idle gap before 7
    reqs = [Request(id=i, prompt=p, max_new_tokens=4, arrival=arrivals[i],
                    traffic_class="chat")
            for i, p in enumerate(prompts)]
    bare_eng = InferenceEngine(cfg)
    bare_log = []
    _record_device_calls(bare_eng, bare_log)
    bare_done, _ = Scheduler(bare_eng).run(reqs)

    router = Router(RouterConfig(serve=cfg, replicas=1,
                                 classes=(ClassSpec("chat"),)))
    router_log = []
    _record_device_calls(router.engines[0], router_log)
    router_done, stats = router.run(reqs)

    assert sorted(router_done) == sorted(bare_done)
    for i in bare_done:
        assert router_done[i].tokens == bare_done[i].tokens, (tp, i)
        assert router_done[i].admitted_step == bare_done[i].admitted_step
    assert len(router_log) == len(bare_log)
    for (kind_a, lg_a), (kind_b, lg_b) in zip(bare_log, router_log):
        assert kind_a == kind_b
        np.testing.assert_array_equal(lg_a, lg_b)
    assert stats.per_class["chat"].ok == 5
    assert sum(stats.per_class["chat"].ttft.steps for _ in [0]) == 5


def test_router_two_replica_mixed_traffic_seed_deterministic():
    """Two runs of the same seeded mixed-traffic stream through one
    2-replica router (reset between) produce identical per-request
    tokens AND identical routing decisions — placement reads only
    deterministic host state (pressure counts, pure prefix probes, the
    sticky family map)."""
    traffic = synthesize_mixed_traffic(
        classes={"chat": dict(rate=0.8, prompt_min=6, prompt_max=10,
                              max_new_tokens=2, families=2,
                              family_prefix_len=4),
                 "bulk": dict(rate=0.4, prompt_min=6, prompt_max=10,
                              max_new_tokens=2)},
        horizon=10, vocab=SPEC.vocab, seed=7, max_requests=12,
    )
    router = Router(RouterConfig(
        serve=ServeConfig(spec=SPEC, slots=2, capacity=32, prefix_slots=2),
        replicas=2,
        classes=(ClassSpec("chat"), ClassSpec("bulk", priority=2)),
        shed_threshold=8,
    ))
    d1, s1 = router.run(traffic)
    router.reset()
    d2, s2 = router.run(traffic)
    assert {i: d1[i].tokens for i in d1} == {i: d2[i].tokens for i in d2}
    assert {i: d1[i].status for i in d1} == {i: d2[i].status for i in d2}
    assert s1.placements == s2.placements
    assert s1.router_sheds == s2.router_sheds
    # Both replicas actually served traffic (the spread is the point).
    assert len(set(s1.placements.values())) == 2
    # Per-class accounting covers every request exactly once.
    assert sum(r.requests for r in s1.per_class.values()) == len(traffic)
    # The SECOND run's SLO stats derive from ITS OWN trace slice only:
    # one TTFT sample per served request, never the previous run's
    # records folded in (a repeated id would pair run 1's `eligible`
    # with run 2's `first_token` — a TTFT spanning the inter-run gap).
    for name, rep in s2.per_class.items():
        assert rep.ttft.steps == rep.ok, (name, rep)


def test_router_affinity_routes_family_to_same_replica():
    """A shared-prefix family lands on ONE replica: the first member
    places by load and seeds the sticky map; staggered siblings follow
    via the live prefix probe (registration landed) or the sticky key
    (co-arrival), so the family never splits — pinned via the route
    trace events and the placement ledger."""
    base = synthesize_prompts(num=1, min_len=9, max_len=9,
                              vocab=SPEC.vocab, seed=11)[0]
    rng = np.random.default_rng(12)
    fam = [np.concatenate([base[:6],
                           rng.integers(1, SPEC.vocab, size=3,
                                        dtype=np.int32)])
           for _ in range(4)]
    reqs = [Request(id=i, prompt=p, max_new_tokens=2, arrival=2 * i,
                    traffic_class="chat")
            for i, p in enumerate(fam)]
    router = Router(RouterConfig(
        serve=ServeConfig(spec=SPEC, slots=2, capacity=32, prefix_slots=2),
        replicas=2, classes=(ClassSpec("chat"),), affinity_window=6,
    ))
    done, stats = router.run(reqs)
    assert all(done[i].status == "ok" for i in range(4))
    replicas = {stats.placements[i] for i in range(4)}
    assert len(replicas) == 1, stats.placements
    assert stats.affinity_placements >= 3  # all but the seeding member
    routes = [r for r in router.tracer.records if r["name"] == "route"]
    assert [r["attrs"]["reason"] for r in routes].count("affinity") >= 3
    # The replica that served the family actually HIT its prefix cache
    # (ServeStats is the replica's product surface).
    k = replicas.pop()
    assert stats.replica[k].prefix_hits >= 1


def test_router_load_balances_without_affinity_signal():
    """Unrelated prompts spread by least backlog: with affinity finding
    nothing (distinct prompts, no families), co-arriving requests split
    across replicas instead of piling onto replica 0."""
    prompts = synthesize_prompts(num=4, min_len=4, max_len=8,
                                 vocab=SPEC.vocab, seed=13)
    reqs = [Request(id=i, prompt=p, max_new_tokens=2,
                    traffic_class="bulk")
            for i, p in enumerate(prompts)]
    router = Router(RouterConfig(
        serve=ServeConfig(spec=SPEC, slots=1, capacity=32),
        replicas=2, classes=(ClassSpec("bulk"),), prefix_affinity=False,
    ))
    done, stats = router.run(reqs)
    assert all(done[i].status == "ok" for i in range(4))
    counts = [sum(1 for v in stats.placements.values() if v == k)
              for k in range(2)]
    assert counts == [2, 2], stats.placements
    assert stats.affinity_placements == 0


def test_router_fully_shed_class_reports_zero_attainment():
    """A class whose every request was shed attained NOTHING: both
    ttft and itl attainment read 0.0 (the vacuous-1.0 ITL escape is
    reserved for classes that actually completed 1-token answers)."""
    chat = Request(id=0, prompt=np.zeros(6, np.int32), max_new_tokens=4,
                   arrival=0, traffic_class="chat")
    bulk = Request(id=1, prompt=np.zeros(6, np.int32), max_new_tokens=4,
                   arrival=1, traffic_class="bulk")
    router = Router(RouterConfig(
        serve=ServeConfig(spec=SPEC, slots=1, capacity=16),
        replicas=1,
        classes=(ClassSpec("chat", priority=0),
                 ClassSpec("bulk", itl_slo_s=1.0, shed_margin=1)),
        shed_threshold=2,
    ))
    done, stats = router.run([chat, bulk])
    assert done[1].status == "shed" and done[0].status == "ok"
    bulk_rep = stats.per_class["bulk"]
    assert bulk_rep.shed == 1 and bulk_rep.ok == 0
    assert bulk_rep.ttft_slo_attained == 0.0
    assert bulk_rep.itl_slo_attained == 0.0
    # The served class keeps its earned attainment.
    assert stats.per_class["chat"].ttft_slo_attained == 1.0


def test_router_validation_and_spec_parsers():
    """Loud-ctor discipline: malformed router configs and spec strings
    are config errors naming the fix, never mid-run surprises."""
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=16)
    with pytest.raises(ValueError, match="replicas"):
        Router(RouterConfig(serve=cfg, replicas=0))
    with pytest.raises(ValueError, match="duplicate traffic class"):
        Router(RouterConfig(serve=cfg, replicas=1,
                            classes=(ClassSpec("a"), ClassSpec("a"))))
    with pytest.raises(ValueError, match="affinity_window"):
        Router(RouterConfig(serve=cfg, replicas=1, affinity_window=1))
    with pytest.raises(ValueError, match="headroom"):
        Router(RouterConfig(serve=cfg, replicas=1,
                            classes=(ClassSpec("bulk", shed_margin=3),),
                            shed_threshold=3))
    router = Router(RouterConfig(serve=cfg, replicas=1,
                                 classes=(ClassSpec("chat"),)))
    with pytest.raises(ValueError, match="unknown traffic_class"):
        router.run([Request(id=0, prompt=np.zeros(4, np.int32),
                            max_new_tokens=1, traffic_class="bulk")])
    with pytest.raises(ValueError, match="duplicate request ids"):
        router.run([
            Request(id=1, prompt=np.zeros(4, np.int32), max_new_tokens=1,
                    traffic_class="chat"),
            Request(id=1, prompt=np.zeros(4, np.int32), max_new_tokens=1,
                    traffic_class="chat"),
        ])

    kw = parse_traffic_spec(
        "horizon=48;seed=3;max_requests=9;burst=10:4:6.5:bulk;"
        "diurnal=0.5:24;"
        "chat:rate=0.6,pmin=8,pmax=24,new=8,families=4,fprefix=6;"
        "bulk:rate=0.3,pmin=8,pmax=32,new=16"
    )
    assert kw["horizon"] == 48 and kw["seed"] == 3
    assert kw["max_requests"] == 9
    assert kw["burst"] == (10, 4, 6.5, "bulk")
    assert kw["diurnal_amplitude"] == 0.5 and kw["diurnal_period"] == 24
    assert kw["classes"]["chat"] == dict(
        rate=0.6, prompt_min=8, prompt_max=24, max_new_tokens=8,
        families=4, family_prefix_len=6,
    )
    with pytest.raises(ValueError, match="unknown traffic key"):
        parse_traffic_spec("bogus=1;chat:rate=1")
    with pytest.raises(ValueError, match="bad key"):
        parse_traffic_spec("chat:rate=1,nope=2")
    with pytest.raises(ValueError, match="no traffic classes"):
        parse_traffic_spec("horizon=8")
    with pytest.raises(ValueError, match="burst takes"):
        parse_traffic_spec("burst=1:2;chat:rate=1")

    specs = parse_slo_spec("chat:ttft=0.5,itl=0.05,priority=0;"
                           "bulk:ttft=60,priority=2,margin=3",
                           {"chat", "bulk", "longdoc"})
    by = {c.name: c for c in specs}
    assert by["chat"].ttft_slo_s == 0.5 and by["chat"].itl_slo_s == 0.05
    assert by["bulk"].priority == 2 and by["bulk"].margin == 3
    assert by["longdoc"].priority == 1  # DEFAULT_CLASS_SPECS fallback
    with pytest.raises(ValueError, match="unknown class"):
        parse_slo_spec("nope:ttft=1", {"chat"})
    with pytest.raises(ValueError, match="bad slo key"):
        parse_slo_spec("chat:frob=1", {"chat"})


@pytest.mark.slow
def test_router_burst_affinity_and_priority_shedding_slow():
    """THE ISSUE 8 scenario pin: a seeded burst overloads a 2-replica
    router. With prefix affinity ON, the chat-class hit rate measurably
    beats affinity OFF (same stream, same replicas), and the overload
    is absorbed by BULK-class router sheds — chat requests all complete
    "ok" — pinned via registry counters ({class=...} labels), trace
    events and the per-replica serve_* registries, not private
    state."""
    traffic = synthesize_mixed_traffic(
        classes={"chat": dict(rate=0.7, prompt_min=8, prompt_max=12,
                              max_new_tokens=2, families=3,
                              family_prefix_len=6),
                 "bulk": dict(rate=0.6, prompt_min=8, prompt_max=12,
                              max_new_tokens=2)},
        horizon=24, vocab=SPEC.vocab, seed=9, burst=(4, 8, 4.0),
        max_requests=28,
    )
    base = RouterConfig(
        serve=ServeConfig(spec=SPEC, slots=2, capacity=32,
                          prefix_slots=3),
        replicas=2,
        classes=(ClassSpec("chat", ttft_slo_s=30.0, priority=0),
                 ClassSpec("bulk", ttft_slo_s=60.0, priority=2)),
        shed_threshold=5,
    )
    hit_rates = {}
    sheds = {}
    for affinity in (True, False):
        reg = MetricRegistry()
        router = Router(dataclasses.replace(base,
                                            prefix_affinity=affinity),
                        registry=reg)
        done, stats = router.run(traffic)
        hits = sum(int(r.counter("serve_prefix_hits_total").value())
                   for r in router.replica_registries)
        lookups = sum(int(r.counter("serve_prefix_lookups_total").value())
                      for r in router.replica_registries)
        hit_rates[affinity] = hits / lookups if lookups else 0.0
        sheds[affinity] = {
            cls: int(reg.counter("router_shed_total").value(
                **{"class": cls}))
            for cls in ("chat", "bulk")
        }
        # Chat absorbed nothing: every chat request completed ok.
        chat = stats.per_class["chat"]
        assert chat.shed == 0 and chat.ok == chat.requests, chat
        assert sheds[affinity]["chat"] == 0
        # The burst DID overload the pool: bulk paid, visibly, both in
        # the class report and the labeled registry counter.
        bulk = stats.per_class["bulk"]
        assert bulk.shed > 0 and sheds[affinity]["bulk"] == bulk.shed
        shed_events = [r for r in router.tracer.records
                       if r["name"] == "router_shed"]
        assert shed_events and all(
            e["attrs"]["cls"] == "bulk" for e in shed_events
        )
        # Per-class SLO accounting spans both classes from ONE trace.
        assert chat.ttft.steps == chat.ok
    # Affinity ON beats OFF on hit rate — the placement policy, not
    # the cache, is what moved (same engines, same stream).
    assert hit_rates[True] > hit_rates[False], hit_rates
