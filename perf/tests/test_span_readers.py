"""The span readers on a trace small enough to work by hand, and on a
second cut from a traced ``serve-1b-closed32`` window on a v5e (PR 26:
the device's programs, its ops merged into the intervals in which any
ran, and the scheduler's host line with the spans' attributes)."""

import json
import os

import pytest

from perf import readers, span_readers as sr, trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_spans_v5e_sample.json")
READERS = ("sched_self_ms", "queue_wait_ms", "decode_launch_ms",
           "logits_fetch_ms", "prefill_ahead_ms", "prefill_pad_pct",
           "idle_engine_pct")
MS = 1_000_000  # the synthetic trace is laid out in whole milliseconds


def ev(name, start_ms, dur_ms, **attrs):
    return [name, int(start_ms * MS), int(dur_ms * MS), attrs]


def engine(kind, start, upload, dispatch, wait, fetch):
    """The four phases of one device call, back to back from ``start``."""
    out, t = [], start
    for name, d in (("upload", upload), ("dispatch", dispatch),
                    ("wait", wait), ("fetch_logits", fetch)):
        out.append(ev(f"engine.{name}", t, d, kind=kind))
        t += d
    return out


def synthetic():
    """Two ticks in a window of 100 ms.

    Tick 0, [2, 40): decode [4, 38) = upload 2, dispatch 1, wait 29,
    fetch 2. The decode program runs on the device over [6.5, 34).
    Tick 1, [42, 98): prefill of request 7 [44, 64) = upload 1,
    dispatch 1, wait 14, fetch 4, its program over [45.5, 59); decode
    [65, 96) = upload 2.5, dispatch 1, wait 25.5, fetch 2, its program
    over [67.5, 93).
    Requests 7 and 8 are submitted between the ticks, at 40.5 and 41;
    8 is not prefilled inside the window.
    """
    host = [ev("perf_window", 0, 100)]
    host += [ev("serve.tick", 2, 38),
             ev("serve.decode", 4, 34, pages=4),
             *engine("decode", 4, 2, 1, 29, 2)]
    host += [ev("serve.submit", 40.5, 0.001, req=7),
             ev("serve.submit", 41, 0.001, req=8)]
    host += [ev("serve.tick", 42, 56),
             ev("serve.prefill", 44, 20, req=7, n=100, bucket=128),
             *engine("prefill", 44, 1, 1, 14, 4),
             ev("serve.decode", 65, 31, pages=4),
             *engine("decode", 65, 2.5, 1, 25.5, 2)]
    modules = [["jit_run_decode_p4(11)", int(6.5 * MS), int(27.5 * MS)],
               ["jit_run_prefill_b128(12)", int(45.5 * MS), int(13.5 * MS)],
               ["jit_run_decode_p4(11)", int(67.5 * MS), int(25.5 * MS)]]
    ops = [["fusion.1", m[1], m[2]] for m in modules]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host},
            {"name": "other/1", "events": [ev("serve.tick", 50, 1)]}]}]}


def context(trace, calls=("decode", "prefill", "decode")):
    return {"span_trace": trace, "trace": trace,
            "facts": {"traced_calls": [{"kind": k} for k in calls]}}


def test_self_time_with_and_without_children():
    ctx = context(synthetic())
    # tick 0: 38 - decode 34 = 4; tick 1: 56 - prefill 20 - decode 31 = 5;
    # the other thread's childless tick of 1 counts whole: median of 1, 4, 5
    assert sr.sched_self_ms(ctx, {}) == 4.0
    only = synthetic()
    only["planes"][1]["lines"][0]["events"] = [
        e for e in only["planes"][1]["lines"][0]["events"]
        if e[0] in ("perf_window", "serve.tick")]
    only["planes"][1]["lines"].pop()
    assert sr.sched_self_ms(context(only), {}) == 47.0  # median of 38, 56


def test_queue_wait_leaves_out_a_request_with_no_prefill_yet():
    ctx = context(synthetic())
    assert sr.queue_wait_ms(ctx, {}) == 3.5  # 44 - 40.5, request 7 alone
    assert ctx["notes"] == {"prefills_in_window": 1,
                            "queue_wait_unserved": 1}


def test_decode_launch_is_the_hosts_alone_and_programs_go_by_name():
    ctx = context(synthetic())
    assert [(p["kind"], p["bucket"]) for p in sr.programs(ctx)] == [
        ("decode", 4), ("prefill", 128), ("decode", 4)]
    # upload + dispatch of the two decode calls: 3 and 3.5
    assert sr.decode_launch_ms(ctx, {}) == 3.25
    # by name as by order; the programs start 0.5 and 0 after their
    # dispatch did and end 2 and 1 before their wait has
    assert ctx["notes"] == {"device_clock_offset_ms": [0.0, 1.0]}
    # the order-based match disagrees: one call more than programs
    off = context(synthetic(), calls=("decode", "decode", "prefill", "decode"))
    sr.decode_launch_ms(off, {})
    assert off["notes"]["program_kinds_disagree"] == {
        "by_name": 3, "by_order": 4, "first_at": 1}
    # a program under another bucket than its span's pages
    other = synthetic()
    other["planes"][0]["lines"][0]["events"][2][0] = "jit_run_decode_p8(13)"
    ctx = context(other)
    assert sr.decode_launch_ms(ctx, {}) == 3.25
    assert ctx["notes"]["decode_programs_other_bucket"] == 1


def test_a_device_clock_a_millisecond_early_moves_no_metric():
    """The profiler aligns the device's clock with the host's to about
    a millisecond: the notes say by how much it must be off, the
    host-only launch and the idle split read as without the skew."""
    skewed = synthetic()
    for ln in skewed["planes"][0]["lines"]:
        for e in ln["events"]:
            e[1] -= int(1.2 * MS)
    ctx = context(skewed)
    assert sr.decode_launch_ms(ctx, {}) == 3.25
    assert ctx["notes"]["device_clock_offset_ms"] == pytest.approx([1.2, 2.2])
    # each gap still starts inside a wait and ends inside an upload
    assert sr.idle_engine_pct(ctx, {}) == pytest.approx(18.5)
    assert ctx["notes"]["idle_sched_pct"] == pytest.approx(9.0)


def test_fetch_prefill_ahead_and_padding():
    ctx = context(synthetic())
    assert sr.logits_fetch_ms(ctx, {}) == 2.0  # the two decode fetches
    # one pair of decodes, [38, 65) between them, the prefill of 20 inside
    assert sr.prefill_ahead_ms(ctx, {}) == 20.0
    assert ctx["notes"] == {"prefills_in_window": 1,
                            "prefill_ahead_share": 1.0}
    assert sr.prefill_pad_pct(ctx, {}) == 100.0 * 28 / 128


@pytest.mark.parametrize("reader", ["queue_wait_ms", "prefill_ahead_ms",
                                    "prefill_pad_pct"])
def test_too_few_prefills_give_none_and_the_count(reader):
    """Each of the three that read ``serve.prefill`` states its sample:
    below the ``min_prefills`` of its file it reads nothing."""
    ctx = context(synthetic())
    assert getattr(sr, reader)(ctx, {"min_prefills": 2}) is None
    assert ctx["notes"] == {"prefills_in_window": 1}
    assert getattr(sr, reader)(ctx, {"min_prefills": 1}) is not None


def test_innermost_span_by_hand():
    spans = [["a", 0, 100], ["b", 10, 30], ["c", 15, 5], ["d", 40, 70],
             ["e", 120, 5]]
    assert sr.innermost(spans) == [
        [0, 10, "a"], [10, 15, "b"], [15, 20, "c"], [20, 40, "b"],
        [40, 100, "d"],  # d would end at 110: cut at its parent's end
        [120, 125, "e"]]


def test_idle_gaps_go_to_the_innermost_span_and_add_up():
    trace = synthetic()
    ctx = context(trace)
    ctx["trace"] = trace
    # Busy [6.5, 34), [45.5, 59), [67.5, 93): idle 33.5 of 100.
    # engine, before each program starts and after it ends:
    #   [4, 6.5) + [44, 45.5) + [65, 67.5) = 6.5
    #   [34, 38) + [59, 64) + [93, 96) = 12
    # sched: [2, 4) + [38, 40) + [42, 44) + [64, 65) + [96, 98) = 9
    # outside any tick: [0, 2) + [40, 42) + [98, 100) = 6
    assert sr.idle_engine_pct(ctx, {}) == pytest.approx(18.5)
    assert ctx["notes"]["idle_sched_pct"] == pytest.approx(9.0)
    assert ctx["notes"]["idle_outside_tick_pct"] == pytest.approx(6.0)
    assert readers.device_idle(ctx, {}) == pytest.approx(33.5)


def test_absent_spans_give_none():
    bare = synthetic()
    bare["planes"][1]["lines"] = [
        {"name": "python3", "events": [ev("perf_window", 0, 100),
                                       ["tick", 2 * MS, 38 * MS, {}]]}]
    for m in bare["planes"][0]["lines"][0]["events"]:
        m[0] = "jit_run(1)"  # the parent's programs
    ctx = context(bare)
    assert [getattr(sr, r)(ctx, {}) for r in READERS] == [None] * 7
    assert "notes" not in ctx
    # the scheduler's spans without the engine's: its own metrics alone
    half = synthetic()
    for ln in half["planes"][1]["lines"]:
        ln["events"] = [e for e in ln["events"]
                        if not e[0].startswith("engine.")]
    ctx = context(half)
    assert sr.logits_fetch_ms(ctx, {}) is None
    assert sr.idle_engine_pct(ctx, {}) is None
    assert ctx["notes"]["idle_sched_pct"] == pytest.approx(9 + 6.5 + 12)


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def test_recorded_sample_holds_the_spans_with_their_attrs(recorded):
    ctx = context(recorded, calls=())
    assert tr.busy(recorded)["window_s"] == 1.0
    ticks = sr.spans(ctx, "serve.tick")
    decodes = sr.spans(ctx, "serve.decode")
    # whole events only: the cut may keep a decode whose tick began before
    assert 10 <= len(ticks) <= 16 and 0 <= len(decodes) - len(ticks) <= 1
    assert all(t[3] == {} for t in ticks)
    assert all(set(d[3]) == {"pages"} and d[3]["pages"] in (8, 16, 32)
               for d in decodes)
    assert all(set(p[3]) == {"req", "n", "bucket"}
               for p in sr.spans(ctx, "serve.prefill"))
    for name in ("engine.upload", "engine.dispatch", "engine.wait",
                 "engine.fetch_logits"):
        # whole events only: the cut may keep the tail of a decode call
        # whose span began before it
        assert 0 <= len(sr.spans(ctx, name, kind="decode")) - len(decodes) <= 1


def test_recorded_sample_reads(recorded):
    ctx = context(recorded)
    del ctx["facts"]
    got = {r: getattr(sr, r)(ctx, {}) for r in READERS}
    assert all(v is not None for v in got.values()), got
    progs = sr.programs(ctx)
    decodes = [p for p in progs if p["kind"] == "decode"]
    assert decodes and all(60e6 < p["duration"] < 66e6 for p in decodes)
    # every decode program under the bucket its name gives, and the
    # two clocks within the millisecond or so the profiler aligns them to
    assert "decode_programs_other_bucket" not in ctx["notes"]
    lo, hi = ctx["notes"]["device_clock_offset_ms"]
    assert -2.0 < lo < hi < 4.0
    assert 0.5 < got["decode_launch_ms"] < 6.0
    assert 0.5 < got["logits_fetch_ms"] < 5.0
    assert 0.05 < got["sched_self_ms"] < 3.0
    assert 0.0 <= got["prefill_pad_pct"] < 50.0
    # the three parts add up to the idle share of the same second
    total = got["idle_engine_pct"] + ctx["notes"]["idle_sched_pct"] \
        + ctx["notes"]["idle_outside_tick_pct"]
    assert total == pytest.approx(readers.device_idle(ctx, {}), abs=1e-9)
    assert 3.0 < total < 25.0
