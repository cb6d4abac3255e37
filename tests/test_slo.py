"""Live SLO control plane tests (ISSUE 10): burn-rate window math
pinned against a brute-force recompute, the seeded burst scenario
firing the bulk-class alert (and only it) deterministically, the
analytic-FLOPs/MFU oracles at rel 1e-6, the /metrics endpoint
byte-identical to the in-process export mid-run, and the off-path pins
(no monitor -> no slo_* metrics)."""

import json
import urllib.error
import urllib.request

import pytest

from ddl_tpu.data.lm import synthesize_mixed_traffic, synthesize_prompts
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry, Tracer
from ddl_tpu.obs import cost
from ddl_tpu.obs.export import MetricsExporter
from ddl_tpu.obs.memory import MemorySampler, record_compile
from ddl_tpu.obs.slo import SloMonitor, SloRule, parse_slo_rules

SPEC = TINY_SPEC


# -- rule validation and grammar ---------------------------------------------


def test_slo_rule_validation():
    ok = SloRule(name="r", metric="m", target_s=0.5)
    assert ok.budget == pytest.approx(0.1)
    with pytest.raises(ValueError, match="exactly one"):
        SloRule(name="r", metric="m")  # neither mode
    with pytest.raises(ValueError, match="exactly one"):
        SloRule(name="r", metric="m", target_s=1.0, total_metric="t")
    with pytest.raises(ValueError, match="objective"):
        SloRule(name="r", metric="m", target_s=1.0, objective=1.0)
    with pytest.raises(ValueError, match="fast_window"):
        SloRule(name="r", metric="m", target_s=1.0, fast_window=8,
                slow_window=8)
    with pytest.raises(ValueError, match="threshold"):
        SloRule(name="r", metric="m", target_s=1.0, threshold=0)
    with pytest.raises(ValueError, match="target_s"):
        SloRule(name="r", metric="m", target_s=-1.0)
    # Dict labels normalize to a sorted tuple (hashable, order-free).
    a = SloRule(name="r", metric="m", target_s=1.0,
                labels={"b": 2, "a": 1})
    assert a.labels == (("a", "1"), ("b", "2"))
    with pytest.raises(ValueError, match="at least one rule"):
        SloMonitor([], MetricRegistry())
    with pytest.raises(ValueError, match="duplicate"):
        SloMonitor([ok, ok], MetricRegistry())


def test_parse_slo_rules_grammar():
    rules = parse_slo_rules(
        "bulk:metric=router_shed_total,total=router_requests_total,"
        "label.class=bulk,objective=0.5,fast=4,slow=8,threshold=2;"
        "ttft:metric=serve_ttft_seconds,target=0.25"
    )
    assert [r.name for r in rules] == ["bulk", "ttft"]
    assert rules[0].total_metric == "router_requests_total"
    assert rules[0].labels == (("class", "bulk"),)
    assert rules[0].objective == 0.5 and rules[0].threshold == 2.0
    assert rules[1].target_s == 0.25 and rules[1].total_metric is None
    for bad, msg in [
        ("", "no rules"),
        ("noname", "NAME:key=val"),
        ("r:target=1", "metric= is required"),
        ("r:metric=m,target=1,bogus=2", "unknown key"),
        ("r:metric=m,target=1;r:metric=m,target=1", "duplicate"),
    ]:
        with pytest.raises(ValueError, match=msg):
            parse_slo_rules(bad)


# -- window math vs brute force ----------------------------------------------


def test_burn_rate_pinned_to_brute_force_recompute():
    """THE window-math pin: the streaming evaluator's per-tick burn
    rates (both windows, histogram AND counter mode) equal a
    brute-force recompute over the test's own full per-tick log —
    including the attach-time baseline, partial-history windows, and
    the edge-triggered alert transitions (alert -> clear -> alert
    counts two)."""
    reg = MetricRegistry()
    h = reg.histogram("lat")
    bad_c = reg.counter("bad")
    tot_c = reg.counter("tot")
    # Pre-attach history must be baseline, not burn.
    h.observe_many([9.0, 9.0])
    bad_c.inc(5, cls="x")
    tot_c.inc(5, cls="x")
    hr = SloRule(name="h", metric="lat", target_s=0.5, objective=0.8,
                 fast_window=3, slow_window=6)
    cr = SloRule(name="c", metric="bad", total_metric="tot",
                 labels={"cls": "x"}, objective=0.5, fast_window=2,
                 slow_window=4)
    mon = SloMonitor([hr, cr], reg)
    # Scripted stream: (histogram samples, counter bad inc, counter
    # total inc) per tick — hot, cooling, idle, hot again.
    script = [
        ([0.9, 0.9], 2, 2), ([0.9, 0.1], 1, 2), ([0.1], 0, 3),
        ([], 0, 0), ([0.1, 0.1], 0, 2), ([0.1], 0, 2),
        ([0.9, 0.9, 0.9], 2, 2), ([0.9, 0.9], 2, 2),
    ]
    # The test's own cumulative log, seeded with the attach baselines.
    log_h = [(2, 2)]
    log_c = [(5, 5)]
    alerts_seen = {"h": 0, "c": 0}

    def brute(rule, log, window):
        i = max(0, len(log) - 1 - window)
        m0, t0 = log[i]
        m1, t1 = log[-1]
        total = t1 - t0
        if total <= 0:
            return 0.0
        return ((m1 - m0) / total) / rule.budget

    for samples, binc, tinc in script:
        h.observe_many(samples)
        if binc:
            bad_c.inc(binc, cls="x")
        if tinc:
            tot_c.inc(tinc, cls="x")
        entered = mon.tick()
        for name in entered:
            alerts_seen[name] += 1
        log_h.append((log_h[-1][0] + sum(1 for v in samples if v > 0.5),
                      log_h[-1][1] + len(samples)))
        log_c.append((log_c[-1][0] + binc, log_c[-1][1] + tinc))
        for rule, log in ((hr, log_h), (cr, log_c)):
            for window, w in (("fast", rule.fast_window),
                              ("slow", rule.slow_window)):
                want = brute(rule, log, w)
                assert mon.burn_rate(rule.name, window) == want
                assert reg.gauge("slo_burn_rate").value(
                    rule=rule.name, window=window
                ) == want
        assert mon.cumulative("h") == log_h[-1]
        assert mon.cumulative("c") == log_c[-1]
    # The histogram rule went hot (ticks 1-2 windows), cooled below
    # threshold, and re-fired on the tail burst: edge-triggered count
    # matches both the monitor's ledger and the registry counter.
    assert mon.alerts("h") == alerts_seen["h"] >= 2
    assert reg.counter("slo_alerts_total").value(rule="h") == \
        mon.alerts("h")
    assert reg.counter("slo_alerts_total").value(rule="c") == \
        mon.alerts("c")
    assert mon.fired_ticks("h")[0] >= 1


# -- serve integration: streaming ≡ post-hoc ---------------------------------


def test_monitor_misses_pinned_to_request_slo_samples():
    """On a live serve run the monitor's cumulative (misses, total)
    equals a brute-force count over ``request_slo_samples`` of the same
    run's trace — the streaming evaluator and the post-hoc derivation
    are one definition. A monitor-less twin run publishes NO slo_*
    metrics (off-path pin), and warmup advances no windows."""
    from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig
    from ddl_tpu.serve.scheduler import request_slo_samples

    prompts = synthesize_prompts(num=3, min_len=4, max_len=8,
                                 vocab=SPEC.vocab, seed=5)
    reqs = [Request(id=i, prompt=p, max_new_tokens=4, arrival=i)
            for i, p in enumerate(prompts)]
    target = 1e-9  # every TTFT on this host misses: misses == total
    rule = SloRule(name="ttft", metric="serve_ttft_seconds",
                   target_s=target, objective=0.5, fast_window=2,
                   slow_window=4)
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=32))
    reg, tr = MetricRegistry(), Tracer()
    mon = SloMonitor([rule], reg, tracer=tr)
    sched = Scheduler(eng, tracer=tr, registry=reg, slo_monitor=mon)
    sched.warmup(reqs)
    assert mon.ticks == 0, "warmup must not advance burn-rate windows"
    assert not tr.records
    done, stats = sched.run(reqs)
    samples = request_slo_samples(tr.records)
    brute_misses = sum(1 for t, _ in samples.values() if t > target)
    assert mon.cumulative("ttft") == (brute_misses, stats.ttft.steps)
    assert brute_misses == 3  # all served requests missed the 1ns target
    assert mon.alerts("ttft") >= 1
    assert any(r["name"] == "slo_alert" and r["attrs"]["rule"] == "ttft"
               for r in tr.records)
    assert reg.counter("slo_alerts_total").value(rule="ttft") == \
        mon.alerts("ttft")

    # Off-path pin: same run shape without a monitor -> the registry
    # holds not one slo_* name.
    eng2 = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=32))
    reg2 = MetricRegistry()
    Scheduler(eng2, registry=reg2).run([
        Request(id=i, prompt=p, max_new_tokens=4, arrival=i)
        for i, p in enumerate(prompts)
    ])
    assert not [m.name for m in reg2.metrics()
                if m.name.startswith("slo_")]


# -- the seeded burst scenario -----------------------------------------------


def _burst_run():
    """One seeded burst run: 1-replica router, slots=1, bulk-targeted
    burst, priority shedding with bulk margin 1 — returns the monitor
    and tracer. Counter-mode rules over the router's live
    {class=}-labeled shed/request counters."""
    from ddl_tpu.serve import ServeConfig
    from ddl_tpu.serve.router import ClassSpec, Router, RouterConfig

    traffic = synthesize_mixed_traffic(
        classes={
            "chat": dict(rate=0.3, prompt_min=4, prompt_max=8,
                         max_new_tokens=2),
            "bulk": dict(rate=0.4, prompt_min=4, prompt_max=8,
                         max_new_tokens=2),
        },
        horizon=16, vocab=SPEC.vocab, seed=0,
        burst=(4, 6, 6.0, "bulk"), max_requests=16,
    )
    rules = tuple(
        SloRule(name=f"{c}_shed", metric="router_shed_total",
                total_metric="router_requests_total",
                labels={"class": c}, objective=0.5, fast_window=3,
                slow_window=6)
        for c in ("bulk", "chat")
    )
    reg, tr = MetricRegistry(), Tracer()
    mon = SloMonitor(rules, reg, tracer=tr)
    cfg = RouterConfig(
        serve=ServeConfig(spec=SPEC, slots=1, capacity=64),
        replicas=1,
        classes=(ClassSpec("chat", priority=0),
                 ClassSpec("bulk", priority=1, shed_margin=1)),
        shed_threshold=2,
    )
    router = Router(cfg, registry=reg, tracer=tr, slo_monitor=mon)
    done, rstats = router.run(traffic)
    return mon, tr, rstats


def test_router_histogram_rule_live_ttft():
    """Histogram-mode rules are LIVE in router mode: the router
    observes router_ttft_seconds{class=} per global tick from the
    shared trace (serve_* histograms land in per-replica registries
    the monitor never sees), so a TTFT rule over it fires mid-run; the
    live series equals the post-hoc request_slo_samples derivation —
    one definition, two consumers. A monitor built on a FOREIGN
    registry is rejected at the ctor."""
    from ddl_tpu.serve import ServeConfig
    from ddl_tpu.serve.router import ClassSpec, Router, RouterConfig
    from ddl_tpu.serve.scheduler import request_slo_samples

    traffic = synthesize_mixed_traffic(
        classes={"chat": dict(rate=0.5, prompt_min=4, prompt_max=8,
                              max_new_tokens=2)},
        horizon=8, vocab=SPEC.vocab, seed=3, max_requests=6,
    )
    rule = SloRule(name="chat_ttft", metric="router_ttft_seconds",
                   labels={"class": "chat"}, target_s=1e-9,
                   objective=0.5, fast_window=2, slow_window=4)
    reg, tr = MetricRegistry(), Tracer()
    mon = SloMonitor([rule], reg, tracer=tr)
    cfg = RouterConfig(serve=ServeConfig(spec=SPEC, slots=2, capacity=32),
                       replicas=1, classes=(ClassSpec("chat"),))
    rec0 = len(tr.records)
    done, _ = Router(cfg, registry=reg, tracer=tr, slo_monitor=mon).run(
        traffic
    )
    # Every served chat request missed the 1ns target, live.
    samples = request_slo_samples(tr.records[rec0:])
    ttfts = sorted(t for t, _ in samples.values())
    assert ttfts and len(done) == len(traffic)
    assert mon.cumulative("chat_ttft") == (len(ttfts), len(ttfts))
    assert mon.alerts("chat_ttft") >= 1
    # The live histogram holds exactly the post-hoc per-request TTFTs.
    assert sorted(reg.histogram("router_ttft_seconds").values(
        **{"class": "chat"}
    )) == ttfts

    with pytest.raises(ValueError, match="different registry"):
        Router(cfg, registry=MetricRegistry(), slo_monitor=mon)
    with pytest.raises(ValueError, match="registry"):
        Router(cfg, slo_monitor=mon)


def test_burn_rate_rejects_unknown_window():
    reg = MetricRegistry()
    mon = SloMonitor(
        [SloRule(name="r", metric="m", target_s=1.0)], reg
    )
    with pytest.raises(ValueError, match="fast.*slow"):
        mon.burn_rate("r", "Fast")


def test_peak_flops_raises_on_unknown_accelerator():
    """An accelerator kind missing from the peak table is an error (a
    utilization anchored to the CPU nominal would read ~4000x high);
    the override still wins, and CPU devices stay silent."""
    import warnings

    class Gpu:
        device_kind = "NVIDIA H100 80GB HBM3"
        platform = "gpu"

    with pytest.raises(cost.UnknownDeviceKind, match="peak-flops"):
        cost.peak_flops_per_device(Gpu())
    assert cost.peak_flops_per_device(Gpu(), override=9.9e14) == 9.9e14
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cost.peak_flops_per_device(None)  # CPU path never warns


def test_burst_scenario_fires_bulk_alert_only_deterministically():
    """THE scenario pin: the seeded bulk burst drives bulk's shed
    fraction over budget — the bulk_shed alert fires — while chat's
    burn stays 0.0 the whole run (green). Two runs from the same seed
    fire at the SAME monitor ticks with the SAME final burns."""
    mon1, tr1, rstats1 = _burst_run()
    # Bulk alerted; chat never did — and never even burned.
    assert mon1.alerts("bulk_shed") >= 1
    assert mon1.fired_ticks("bulk_shed")
    assert mon1.alerts("chat_shed") == 0
    assert mon1.burn_rate("chat_shed", "fast") == 0.0
    assert mon1.burn_rate("chat_shed", "slow") == 0.0
    assert mon1.cumulative("chat_shed")[0] == 0  # zero chat sheds
    assert rstats1.per_class["bulk"].shed > 0
    assert rstats1.per_class["chat"].shed == 0
    # Attempts include sheds: router_requests_total counts EVERY
    # arrival of the class (counted before the shed decision), so an
    # all-shed window has a non-empty denominator and burns — the
    # worst overload can never read 0.0.
    for c in ("bulk", "chat"):
        assert mon1.registry.counter("router_requests_total").value(
            **{"class": c}
        ) == rstats1.per_class[c].requests
    # The alert is in the trace, attributed to the bulk rule only.
    alert_rules = {r["attrs"]["rule"] for r in tr1.records
                   if r["name"] == "slo_alert"}
    assert alert_rules == {"bulk_shed"}

    # Determinism: a fresh router/registry/monitor from the same seed
    # replays the identical alert timeline.
    mon2, _, rstats2 = _burst_run()
    assert mon2.fired_ticks("bulk_shed") == mon1.fired_ticks("bulk_shed")
    assert mon2.alerts("bulk_shed") == mon1.alerts("bulk_shed")
    for name in ("bulk_shed", "chat_shed"):
        assert mon2.cumulative(name) == mon1.cumulative(name)
        for w in ("fast", "slow"):
            assert mon2.burn_rate(name, w) == mon1.burn_rate(name, w)
    assert rstats2.per_class["bulk"].shed == rstats1.per_class["bulk"].shed


# -- analytic FLOPs / MFU oracles --------------------------------------------


def test_lm_flops_match_hand_computed_oracle():
    """train_mfu's numerator for one LM config vs an independently
    hand-written arithmetic expansion, at rel 1e-6 (they are integers —
    the tolerance is the acceptance bar's, equality is the reality)."""
    # LMSpec: vocab=32, d_model=32, heads=2, layers=2, d_ff=64.
    B, T, e, f, v, L = 4, 32, 32, 64, 32, 2
    qkvo = 8 * T * e * e            # 4 projections, 2*T*e*e each
    attn = 4 * T * T * e            # QK^T + AV over the full T x T
    mlp = 4 * T * e * f             # w1 + w2
    head = 2 * B * T * e * v
    fwd = L * B * (qkvo + attn + mlp) + head
    assert cost.lm_forward_flops(SPEC, B, T) == pytest.approx(
        fwd, rel=1e-6
    )
    assert cost.lm_forward_flops(SPEC, B, T) == fwd
    assert cost.lm_train_step_flops(SPEC, B, T) == 3 * fwd
    # remat recomputes the blocks' forward (not the head) once more.
    assert cost.lm_train_step_flops(SPEC, B, T, remat=True) == \
        3 * fwd + L * B * (qkvo + attn + mlp)


def test_cnn_flops_match_hand_computed_oracle():
    """Same bar for the CNN family at the tiny widths: each SAME conv
    is 2*H*W*cout*(25*cin), pools/bias/relu uncounted, three FCs."""
    batch = 10
    conv = (2 * 28 * 28 * 4 * (25 * 1)
            + 2 * 14 * 14 * 8 * (25 * 4)
            + 2 * 7 * 7 * 8 * (25 * 8)
            + 2 * 4 * 4 * 8 * (25 * 8))
    fc = 2 * (2 * 2 * 8) * 32 + 2 * 32 * 16 + 2 * 16 * 10
    fwd = conv + fc
    got = cost.cnn_train_step_flops(batch, (4, 8, 8, 8), (32, 16))
    assert got == pytest.approx(3 * batch * fwd, rel=1e-6)
    assert got == 3 * batch * fwd
    # The full-width default is the reference model.
    assert cost.cnn_forward_flops() == cost.cnn_forward_flops(
        (32, 64, 128, 256), (1024, 512), 10, 1
    )


def test_serve_flops_paged_aware_and_peak_table():
    """Decode FLOPs track the ATTENDED width — the paged bucket's
    residency vs the contiguous capacity — and the peak table resolves
    device kinds with the override winning."""
    e, f, v, L = 32, 64, 32, 2
    per_tok = lambda W: L * (8 * e * e + 4 * e * W + 4 * e * f) + 2 * e * v
    assert cost.serve_decode_flops_per_token(SPEC, 16) == per_tok(16)
    assert cost.serve_decode_flops_per_token(SPEC, 256) == per_tok(256)
    # Paged residency of 2 pages x 8 rows vs a 256-row ring: the
    # attention term shrinks 16x, everything else is identical.
    small, big = per_tok(16), per_tok(256)
    assert big - small == L * 4 * e * (256 - 16)
    assert cost.serve_prefill_flops(SPEC, 8, 64) == \
        L * (8 * 8 * e * e + 4 * 8 * 64 * e + 4 * 8 * e * f) \
        + 2 * 8 * e * v

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    assert cost.peak_flops_per_device(Dev("TPU v4")) == 275e12
    assert cost.peak_flops_per_device(Dev("TPU v5p slice")) == 459e12
    assert cost.peak_flops_per_device(Dev("cpu")) == \
        cost.CPU_NOMINAL_PEAK_FLOPS
    assert cost.peak_flops_per_device(None) == cost.CPU_NOMINAL_PEAK_FLOPS
    assert cost.peak_flops_per_device(Dev("TPU v4"), override=1e12) == 1e12
    with pytest.raises(ValueError):
        cost.peak_flops_per_device(None, override=-1)
    assert cost.mfu(1e10, 0.5, 2, 1e10) == pytest.approx(1.0)
    assert cost.mfu(1e10, 0.0, 2, 1e10) == 0.0


def test_train_mfu_gauge_matches_recompute_lm_and_cnn():
    """Integration: the train_mfu gauge each trainer publishes equals
    the analytic FLOPs over the SAME span bracket the registry's
    train_span_seconds histogram recorded, under a --peak-flops
    override (exact floats — one formula, two evaluation sites)."""
    from ddl_tpu.data import load_mnist
    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
    from ddl_tpu.train import SingleChipTrainer, TrainConfig

    peak = 1e12
    # LM: one span of one step.
    ds = synthesize_copy(num_train=8, num_test=8, seq_len=32,
                         vocab=SPEC.vocab, seed=0)
    cfg = SeqConfig(epochs=1, batch_size=8, num_workers=1, scheme="full",
                    eval_every=0, spec=SPEC)
    reg = MetricRegistry()
    SeqTrainer(cfg, ds).train(log=lambda s: None, metrics=reg,
                              peak_flops=peak)
    span_s = reg.histogram("train_span_seconds").values()[-1]
    flops = cost.lm_train_step_flops(SPEC, 8, 32)
    assert reg.gauge("train_mfu").value() == \
        cost.mfu(flops * 1, span_s, 1, peak)
    assert reg.counter("xla_compiles_total").value(kind="train_span") >= 1

    # CNN: narrow model, one span of one step.
    mnist = load_mnist(path=None, synthetic_train=64, synthetic_test=16,
                       seed=7)
    tcfg = TrainConfig(epochs=1, batch_size=64, eval_every=0, seed=0,
                       conv_channels=(4, 8, 8, 8), fc_sizes=(32, 16))
    reg2 = MetricRegistry()
    SingleChipTrainer(tcfg, mnist).train(log=lambda s: None, metrics=reg2,
                                         peak_flops=peak)
    span_s2 = reg2.histogram("train_span_seconds").values()[-1]
    flops2 = cost.cnn_train_step_flops(64, (4, 8, 8, 8), (32, 16))
    assert reg2.gauge("train_mfu").value() == \
        cost.mfu(flops2 * 1, span_s2, 1, peak)
    assert reg2.counter("xla_compiles_total").value(kind="eval") >= 1


# -- /metrics endpoint --------------------------------------------------------


def test_metrics_endpoint_byte_identical_during_live_serve_run():
    """THE export pin: mid-run (externally-driven scheduler, between
    ticks) GET /metrics returns EXACTLY the bytes of the in-process
    prometheus_text() — the endpoint is transport, not a second
    formatter. Plus /healthz and the 404 path."""
    from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig

    prompts = synthesize_prompts(num=2, min_len=4, max_len=8,
                                 vocab=SPEC.vocab, seed=2)
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=32))
    reg = MetricRegistry()
    sched = Scheduler(eng, registry=reg)
    with MetricsExporter(reg, 0) as exp:
        sched.begin()
        for i, p in enumerate(prompts):
            sched.submit(Request(id=i, prompt=p, max_new_tokens=4))
        for _ in range(3):
            sched.tick()
        # Mid-run, between ticks: nothing mutates the registry while
        # the handler snapshots, so equality is byte-exact.
        body = urllib.request.urlopen(exp.url("/metrics")).read()
        assert body == reg.prometheus_text().encode("utf-8")
        assert b"serve_decode_tokens_total" in body
        while not sched.idle:
            sched.tick()
        done, _ = sched.collect()
        assert len(done) == 2
        body2 = urllib.request.urlopen(exp.url("/metrics")).read()
        assert body2 == reg.prometheus_text().encode("utf-8")
        health = json.loads(urllib.request.urlopen(
            exp.url("/healthz")
        ).read())
        # ISSUE 11: /healthz carries the compact goodput digest next
        # to liveness — equal to the live gauge, absent keys for
        # detectors this run never attached.
        assert health["status"] == "ok"
        assert health["goodput_fraction"] == \
            reg.gauge("goodput_fraction").value()
        assert "last_anomaly_tick" not in health
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(exp.url("/nope"))
        assert e.value.code == 404


# -- memory watermarks + compile counters ------------------------------------


def test_memory_sampler_guarded_and_latching():
    """memory_stats()-less backends (this XLA:CPU) latch the sampler
    off after one probe; a reporting device fills the watermark
    gauges."""
    import jax

    reg = MetricRegistry()
    s = MemorySampler(reg, [jax.devices()[0]])
    first = s.sample()
    if not first:  # this container: CPU returns None
        assert s.supported is False
        assert s.sample() is False  # latched: no re-probe
        assert not [m.name for m in reg.metrics()]

    class FakeDev:
        @staticmethod
        def memory_stats():
            return {"bytes_in_use": 10, "peak_bytes_in_use": 20,
                    "bytes_limit": 100}

    class DeadDev:
        @staticmethod
        def memory_stats():
            raise RuntimeError("no stats on this backend")

    reg2 = MetricRegistry()
    s2 = MemorySampler(reg2, [FakeDev(), DeadDev()])
    assert s2.sample() is True and s2.supported is True
    assert reg2.gauge("device_memory_bytes_in_use").value(device=0) == 10
    assert reg2.gauge("device_memory_peak_bytes").value(device=0) == 20
    assert reg2.gauge("device_memory_bytes_limit").value(device=0) == 100
    assert reg2.gauge("device_memory_bytes_in_use").value(device=1) is None


def test_compile_counters_and_spans():
    """record_compile moves the counter, observes the bracket when
    given one (a real span in the trace), and degrades to an event
    without one; the engine's builds feed it through the scheduler
    hook (pinned live in test_train_mfu / the serve integration
    above)."""
    reg, tr = MetricRegistry(), Tracer()
    record_compile(reg, tr, "train_span", t0=1.0, t1=1.5, k=3)
    record_compile(reg, tr, "prefill", key=8)
    record_compile(None, tr, "decode")  # registry-less: trace only
    record_compile(reg, None, "decode")  # tracer-less: count only
    assert reg.counter("xla_compiles_total").value(kind="train_span") == 1
    assert reg.counter("xla_compiles_total").value(kind="prefill") == 1
    assert reg.counter("xla_compiles_total").value(kind="decode") == 1
    assert reg.histogram("xla_compile_seconds").values(
        kind="train_span"
    ) == [0.5]
    names = [(r["name"], r["type"]) for r in tr.records]
    assert names == [("compile", "span"), ("compile", "event"),
                     ("compile", "event")]
    assert tr.records[0]["dur_s"] == pytest.approx(0.5)
