"""The clock readers on a synthetic window laid out by hand, with the
device's clock set off by a known amount, and on two seconds cut from
a traced ``serve-1b-closed32`` window on a v5e (PR 37, seed 3700100003,
from 2 s into the window by ``span_readers.sample``: the device's
programs, the program's spans with their attributes, and the runtime's
markers on every host thread)."""

import json
import os

import pytest

from perf import clock_readers as cr, span_readers as sr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_clock_v5e_sample.json")
READERS = ("wait_tail_ms", "start_lag_ms", "h2d_ms")
MS = 1_000_000  # laid out in milliseconds, kept to whole nanoseconds


def ev(name, start_ms, dur_ms, **attrs):
    return [name, round(start_ms * MS), round(dur_ms * MS), attrs]


# per decode call: the device's start after the enqueue began, and the
# done-flag's read after the device's end (ms, the host's clock)
LATE_START = (0.05, 0.3, 0.1)
LATE_FLAG = (0.2, 0.1, 0.15)


def synthetic(offset_ms=-1.0, late_start=LATE_START, late_flag=LATE_FLAG):
    """Three decode calls at 5, 40 and 70 ms of a 100-ms window and a
    prefill between the first two. A decode call ``[d, d + 20)``:
    ``engine.upload`` ``[d, d + 2)`` holding ``engine.h2d`` ``[d + 0.5,
    d + 1.7)``, ``engine.dispatch`` ``[d + 2, d + 2.6)`` holding the
    launch ``[d + 2.1, d + 2.5)``, the enqueue on another thread from
    ``d + 2.55``; the program runs 14 ms from ``d + 2.55 + late_start``
    on the host's clock; the flag is read 0.2 ms from its end +
    ``late_flag``, the ids land 0.6 after its end, ``engine.wait`` ends
    1.0 after it. The device's lines are written on a clock that
    ``offset_ms`` added to puts on the host's."""
    host, markers, modules = [ev("perf_window", 0, 100)], [], []
    for k, d in enumerate((5, 40, 70)):
        start = d + 2.55 + late_start[k]
        end = start + 14
        host += [ev("serve.tick", d - 1, 22),
                 ev("serve.decode", d, 20, pages=4),
                 ev("engine.upload", d, 2, kind="decode"),
                 ev("engine.h2d", d + 0.5, 1.2, kind="decode", arrays=5,
                    bytes=5024 + 16 * k),
                 ev("engine.dispatch", d + 2, 0.6, kind="decode"),
                 ev("engine.wait", d + 2.6, end + 1.0 - d - 2.6,
                    kind="decode"),
                 ev("engine.fetch_logits", end + 1.0, 0.01, kind="decode")]
        markers += [ev(cr.LAUNCH, d + 2.1, 0.4),
                    ev(cr.ENQUEUE, d + 2.55, 0.05),
                    ev(cr.DONE, end + late_flag[k], 0.2),
                    ev(cr.LANDED, end + 0.55, 0.05)]
        modules.append([f"jit_run_decode_p4({k})",
                        round((start - offset_ms) * MS), 14 * MS])
    # a prefill, whose own launch and flag no decode program may take
    host += [ev("serve.tick", 27, 11),
             ev("serve.prefill", 28, 8, req=1, n=100, bucket=128),
             ev("engine.dispatch", 28.5, 0.5, kind="prefill"),
             ev("engine.wait", 29, 6, kind="prefill")]
    markers += [ev(cr.LAUNCH, 28.6, 0.3), ev(cr.ENQUEUE, 29, 0.05),
                ev(cr.DONE, 34.6, 0.1)]
    modules.append(["jit_run_prefill_b128(9)",
                    round((29.5 - offset_ms) * MS), 5 * MS])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host},
            {"name": "main/1", "events": [m for m in markers
                                          if m[0] == cr.LAUNCH]},
            {"name": "tfrt-non-blocking-queue/2",
             "events": [m for m in markers if m[0] == cr.ENQUEUE]},
            {"name": "futex-default-SDomainT/3",
             "events": [m for m in markers if m[0] in (cr.DONE, cr.LANDED)]}]}]}


def context(trace):
    return {"clock_trace": trace}


def read(ctx):
    return {r: getattr(cr, r)(ctx, {}) for r in READERS}


@pytest.mark.parametrize("offset_ms", [-1.0, 0.0, 0.7])
def test_bracket_holds_the_offset_and_the_metrics_ignore_it(offset_ms):
    ctx = context(synthetic(offset_ms))
    got = read(ctx)
    # lo from the earliest start (0.05 after its enqueue), hi from the
    # earliest flag (0.1 after its end): [offset - 0.05, offset + 0.1]
    lo, hi = ctx["notes"]["clock_pin_ms"]
    assert lo <= offset_ms <= hi
    assert (lo, hi) == pytest.approx((offset_ms - 0.05, offset_ms + 0.1))
    assert ctx["notes"]["clock_pin_calls"] == 3
    assert ctx["notes"]["clock_pin_dropped"] == 0
    assert ctx["notes"]["clock_pin_outside"] == 0
    # delta lies 0.025 after the true offset: each program is read that
    # much later than it ran
    assert got["wait_tail_ms"] == pytest.approx(1.0 - 0.025)
    assert ctx["notes"]["wait_tail_split_ms"] == pytest.approx(
        [0.6 - 0.025, 0.4])
    # 0.1 to the launch, 0.45 to the enqueue, then 0.05 / 0.3 / 0.1
    assert got["start_lag_ms"] == pytest.approx(0.55 + 0.1 + 0.025)
    assert ctx["notes"]["start_lag_split_ms"] == pytest.approx(
        [0.1, 0.45 + 0.1 + 0.025])
    assert got["h2d_ms"] == pytest.approx(1.2)
    assert (ctx["notes"]["h2d_arrays"], ctx["notes"]["h2d_bytes"]) == (
        5, 5040)


@pytest.mark.parametrize("case,why", [
    # two of three flags read before their programs have ended
    ({"late_flag": (0.2, -0.5, -0.6)}, "markers disagree: 1 of 3"),
    # every program starts a long way after its launch
    ({"late_start": (0.6, 0.7, 0.8)}, "wide"),
])
def test_inconsistent_or_loose_markers_pin_nothing(case, why):
    ctx = context(synthetic(**case))
    got = read(ctx)
    assert got["wait_tail_ms"] is None and got["start_lag_ms"] is None
    assert why in ctx["notes"]["clock_pin_refused"]
    assert got["h2d_ms"] == pytest.approx(1.2)  # needs no pin


@pytest.mark.parametrize("case", [
    # one flag read before its program has ended: an empty bracket
    {"late_flag": (0.2, -0.5, 0.15)},
    # one program that seems to start 0.3 before its enqueue: a bracket
    # of its own that the other two miss
    {"late_start": (0.05, -0.3, 0.1), "late_flag": (0.2, 0.5, 0.15)},
])
def test_one_call_out_of_line_is_dropped_from_the_pin(case):
    ctx = context(synthetic(**case))
    got = read(ctx)
    # the other two share [offset - 0.05, offset + 0.15]
    assert ctx["notes"]["clock_pin_ms"] == pytest.approx([-1.05, -0.85])
    assert ctx["notes"]["clock_pin_dropped"] == 1
    assert "clock_pin_refused" not in ctx["notes"]
    assert got["wait_tail_ms"] is not None and got["start_lag_ms"] is not None


def test_an_earlier_flag_read_is_not_the_one_that_saw_the_end():
    """A second read of the flag, before the second program has ended:
    the pin takes the read that the ids' copy follows."""
    trace = synthetic()
    flags = trace["planes"][1]["lines"][3]["events"]
    # the second call's program ends at 40 + 2.55 + 0.3 + 14 (host clock)
    flags.append(ev(cr.DONE, 40 + 2.55 + 0.3 + 14 - 0.3, 0.2))
    flags.sort(key=lambda e: e[1])
    ctx = context(trace)
    read(ctx)
    assert ctx["notes"]["clock_pin_ms"] == pytest.approx([-1.05, -0.9])
    assert ctx["notes"]["clock_pin_dropped"] == 0


@pytest.mark.parametrize("brackets,share,stretch", [
    ([], 0, None),
    ([[0, 1], [2, 1]], 1, [0, 1]),
    ([[0, 4], [1, 5], [3, 6], [7, 8]], 3, [3, 4]),
    ([[0, 1], [1, 2]], 2, [1, 1]),
    ([[0, 1], [2, 3]], 1, [0, 1]),
])
def test_agreed_finds_the_stretch_most_brackets_share(brackets, share, stretch):
    assert cr.agreed(brackets) == (share, stretch)


@pytest.mark.parametrize("drop,empty", [
    (set(cr.MARKERS), ("wait_tail_ms", "start_lag_ms")),
    ({cr.LANDED}, ("wait_tail_ms", "start_lag_ms")),
    ({"engine.h2d"}, ("h2d_ms",)),
    ({"engine.h2d", cr.ENQUEUE}, READERS),
])
def test_absent_markers_or_spans_give_none(drop, empty):
    trace = synthetic()
    for ln in trace["planes"][1]["lines"]:
        ln["events"] = [e for e in ln["events"] if e[0] not in drop]
    ctx = context(trace)
    got = read(ctx)
    assert [r for r in READERS if got[r] is None] == list(empty)
    if set(empty) >= {"wait_tail_ms", "start_lag_ms"}:
        assert "clock_pin_ms" not in ctx.get("notes", {})


def test_parent_programs_read_nothing():
    """The programs before PR 26 were all ``jit_run`` and wrote no
    ``engine.*`` span: nothing to match, nothing raised, no notes."""
    bare = synthetic()
    for m in bare["planes"][0]["lines"][0]["events"]:
        m[0] = "jit_run(1)"
    bare["planes"][1]["lines"][0]["events"] = [
        e for e in bare["planes"][1]["lines"][0]["events"]
        if not e[0].startswith("engine.")]
    ctx = context(bare)
    assert read(ctx) == dict.fromkeys(READERS)
    assert "notes" not in ctx


def test_sample_cuts_this_view_too():
    """``span_readers.sample`` records this module's view (markers
    and all) as it records its own."""
    cut = sr.sample(synthetic(), 0.030, 0.002)
    host = cut["planes"][1]["lines"][0]["events"]
    assert host[-1][:3] == ["perf_window", 2 * MS, 30 * MS]
    assert all(2 * MS <= e[1] and e[1] + e[2] <= 32 * MS for e in host)
    ctx = context(cut)
    assert read(ctx)["h2d_ms"] == pytest.approx(1.2)
    assert ctx["notes"]["clock_pin_calls"] == 1


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def test_recorded_sample_reads_on_one_clock(recorded):
    ctx = context(recorded)
    got = read(ctx)
    assert all(v is not None for v in got.values()), (got, ctx["notes"])
    notes = ctx["notes"]
    lo, hi = notes["clock_pin_ms"]
    assert 0 <= hi - lo <= 0.2 and notes["clock_pin_calls"] >= 10
    assert notes["clock_pin_dropped"] == 0
    assert notes["clock_pin_outside"] == 0
    assert 0.2 < got["wait_tail_ms"] < 3.0
    assert 0.2 < got["start_lag_ms"] < 3.0
    assert 0.1 < got["h2d_ms"] < 3.0
    assert notes["h2d_arrays"] == 5  # tokens, lengths, ids, mask, table
    assert sum(notes["wait_tail_split_ms"]) == pytest.approx(
        got["wait_tail_ms"], abs=0.3)
