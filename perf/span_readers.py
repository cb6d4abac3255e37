"""Readers of the program's own spans (``program_span`` and
``program_counter`` metrics), named in a metric's file as
``"reader": "span_readers.<function>"``.

The serve path writes ``jax.profiler.TraceAnnotation`` spans
(``ddl_tpu/obs/trace.py:span``): ``serve.submit`` (``req``),
``serve.tick`` around ``serve.prefill`` (``req``, ``n``, ``bucket``) and
``serve.decode`` (``pages``), and inside those two device calls
``engine.upload`` / ``engine.dispatch`` / ``engine.wait`` /
``engine.fetch_logits`` (``kind``). They land on ``/host:CPU`` of the
same ``.xplane.pb`` as the device's lines, their attributes as the
events' stats; the engine's programs are named
``jit_run_prefill_b<bucket>`` and ``jit_run_decode_p<pages>``.

The profiler aligns the device's clock with the host's anew each
session, to about a millisecond (PERF.md section 6, PR 26). So a metric
here is a duration on ONE clock: host spans alone, or device gaps
attributed to host spans so coarse that a millisecond cannot move time
from one to the other. What the two clocks say of each other goes to
``notes`` (``device_clock_offset_ms``).

``trace_reduce.load`` drops event stats, so this module loads its own
view of ``ctx["trace_path"]``: the same plain form, the program's spans
as ``[name, start_ns, duration_ns, attrs]``. Interval arithmetic and
the window are ``trace_reduce``'s. A program that writes no such span
(the parent of the PR that added them) gives ``None`` from every reader
here: nothing raises.
"""

from __future__ import annotations

import re
import sys

import numpy as np

from . import harness, trace_reduce as tr

SPAN = re.compile(r"^(serve|engine)\.")
PREFILL, DECODE = "serve.prefill", "serve.decode"
PROGRAM = re.compile(r"^jit_run_(prefill|decode)_[a-z](\d*)")
LAUNCH = ("engine.upload", "engine.dispatch")


def load(path: str) -> dict:
    """The view of an ``.xplane.pb`` the readers here need: per chip
    the executed programs and ops, of the host the window's span and
    the program's spans with their stats."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = tr.DEVICE_PLANE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                events = [[tr.op_name(ev.name), int(ev.start_ns),
                           int(ev.duration_ns)] for ev in line.events]
            else:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           {k: v for k, v in ev.stats}]
                          for ev in line.events
                          if ev.name == tr.WINDOW or SPAN.match(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def view(ctx: dict) -> dict:
    """This module's view of the run's trace, loaded once a run."""
    if "span_trace" not in ctx:
        ctx["span_trace"] = load(ctx["trace_path"])
    return ctx["span_trace"]


def host_lines(trace: dict) -> list:
    """The events of each host thread, a list a thread."""
    return [ln["events"] for plane in trace["planes"]
            if plane["name"] == "/host:CPU" for ln in plane["lines"]]


def thread_spans(trace: dict) -> list:
    """The program's spans of the scheduler's thread (the host line
    with most ``serve.tick`` spans), by start, the enclosing first."""
    def ticks(events):
        return sum(e[0] == "serve.tick" for e in events)

    best = max(host_lines(trace), key=ticks, default=[])
    if not ticks(best):
        return []
    return sorted((e for e in best if SPAN.match(e[0])),
                  key=lambda e: (e[1], -e[2]))


def spans(ctx: dict, name: str, **attrs) -> list:
    """The spans of one name that lie inside the traced window, with
    the given attributes, by start."""
    trace = view(ctx)
    t0, t1 = tr.window_ns(trace)
    return sorted((e for events in host_lines(trace) for e in events
                   if e[0] == name and e[1] >= t0 and e[1] + e[2] <= t1
                   and all(e[3].get(k) == v for k, v in attrs.items())),
                  key=lambda e: e[1])


def _inside(children: list, parent) -> list:
    s, e = parent[1], parent[1] + parent[2]
    return [c for c in children if c[1] >= s and c[1] + c[2] <= e]


def _median_ms(durations_ns):
    return harness.median(durations_ns) / 1e6 if durations_ns else None


def sched_self_ms(ctx: dict, args: dict):
    """Median over ticks of ``serve.tick`` less what its
    ``serve.prefill`` / ``serve.decode`` children cover: the scheduler's
    own Python, admission and emission."""
    calls = spans(ctx, PREFILL) + spans(ctx, DECODE)
    return _median_ms([t[2] - sum(c[2] for c in _inside(calls, t))
                       for t in spans(ctx, "serve.tick")])


def _prefills(ctx: dict, args: dict):
    """The window's ``serve.prefill`` spans, their number under
    ``notes``: the sample of the three metrics that read them. ``None``
    below the ``min_prefills`` of the metric's file."""
    got = spans(ctx, PREFILL)
    if got:
        ctx.setdefault("notes", {})["prefills_in_window"] = len(got)
    return got if len(got) >= args.get("min_prefills", 1) else None


def queue_wait_ms(ctx: dict, args: dict):
    """Median over the requests submitted in the window of
    ``serve.submit`` to the start of that request's first
    ``serve.prefill``. A request with no prefill yet is left out and
    counted under ``notes``."""
    prefills = _prefills(ctx, args)
    if prefills is None:
        return None
    first: dict = {}
    for p in prefills:
        first.setdefault(p[3]["req"], p[1])
    waits, unserved = [], 0
    for s in spans(ctx, "serve.submit"):
        if s[3]["req"] in first:
            waits.append(first[s[3]["req"]] - s[1])
        else:
            unserved += 1
    if unserved:
        ctx.setdefault("notes", {})["queue_wait_unserved"] = unserved
    return _median_ms(waits)


def programs(ctx: dict) -> list:
    """The engine's executed programs of the window on the first chip,
    told apart by NAME: ``{"kind", "bucket", "start", "duration"}`` in
    order. Notes where the kinds so read differ from the order-based
    match the accepted readers make (k-th program = k-th call the
    recorder saw)."""
    trace = view(ctx)
    t0, t1 = tr.window_ns(trace)
    planes = tr.device_planes(trace)
    if not planes:
        return []
    out = []
    for name, start, dur in sorted(tr.line_events(planes[0], "XLA Modules"),
                                   key=lambda e: e[1]):
        m = PROGRAM.match(name)
        if m and start >= t0 and start + dur <= t1:
            out.append({"kind": m[1], "bucket": int(m[2] or 0),
                        "start": start, "duration": dur})
    calls = ctx.get("facts", {}).get("traced_calls")
    if out and calls is not None:
        by_order = [c["kind"] for c in calls]
        by_name = [p["kind"] for p in out]
        if by_order != by_name:
            ctx.setdefault("notes", {})["program_kinds_disagree"] = {
                "by_name": len(by_name), "by_order": len(by_order),
                "first_at": next((i for i, (a, b) in
                                  enumerate(zip(by_name, by_order))
                                  if a != b), min(len(by_name),
                                                  len(by_order)))}
    return out


def check_clocks(ctx: dict) -> None:
    """What the device's lines and the host's spans say of each other,
    to ``notes``. A decode program (matched to the ``serve.decode`` span
    it overlaps most) cannot start before its ``engine.dispatch`` does
    nor end after its ``engine.wait`` has: ``device_clock_offset_ms`` is
    ``[lo, hi]``, the least and the most that added to every device time
    makes that so for all of them, 0 inside it where the two clocks
    agree. ``decode_programs_other_bucket`` counts the programs whose
    name's bucket is not the span's ``pages``."""
    decodes = spans(ctx, DECODE)
    dispatches = spans(ctx, "engine.dispatch", kind="decode")
    waits = spans(ctx, "engine.wait", kind="decode")
    lo, hi, other_bucket = None, None, 0
    for p in programs(ctx):
        if p["kind"] != "decode":
            continue
        start, end = p["start"], p["start"] + p["duration"]
        shared, host = max(((min(end, d[1] + d[2]) - max(start, d[1]), d)
                            for d in decodes), key=lambda x: x[0],
                           default=(0, None))
        if shared <= 0:
            continue
        other_bucket += host[3].get("pages") != p["bucket"]
        for d in _inside(dispatches, host):
            lo = d[1] - start if lo is None else max(lo, d[1] - start)
        for w in _inside(waits, host):
            gap = w[1] + w[2] - end
            hi = gap if hi is None else min(hi, gap)
    if lo is not None and hi is not None:
        ctx.setdefault("notes", {})["device_clock_offset_ms"] = \
            [lo / 1e6, hi / 1e6]
    if other_bucket:
        ctx.setdefault("notes", {})["decode_programs_other_bucket"] = \
            other_bucket


def decode_launch_ms(ctx: dict, args: dict):
    """Median over the decode calls of ``engine.upload`` plus
    ``engine.dispatch``: the host's time from the call of
    ``engine.decode`` to the return of the compiled program's call, when
    the device has the work. Host spans alone (the device start of the
    program would read the session's clock skew with it)."""
    launches = [s for n in LAUNCH for s in spans(ctx, n, kind="decode")]
    check_clocks(ctx)
    return _median_ms([sum(c[2] for c in _inside(launches, d))
                       for d in spans(ctx, DECODE)])


def logits_fetch_ms(ctx: dict, args: dict):
    """Median ``engine.fetch_logits`` of a decode call: the
    ``[slots, vocab]`` fp32 logits brought to the host every tick."""
    return _median_ms([s[2] for s in spans(ctx, "engine.fetch_logits",
                                           kind="decode")])


def prefill_ahead_ms(ctx: dict, args: dict):
    """Mean, over the decode ticks that had any, of the ``serve.prefill``
    time between the previous ``serve.decode``'s end and this one's
    start: what a new prompt adds to the token gap of every other
    request. The share of such ticks goes to ``notes``."""
    decodes, prefills = spans(ctx, DECODE), _prefills(ctx, args)
    if len(decodes) < 2 or prefills is None:
        return None
    ahead = []
    for prev, cur in zip(decodes, decodes[1:]):
        between = [prev[0], prev[1] + prev[2], cur[1] - prev[1] - prev[2]]
        got = sum(p[2] for p in _inside(prefills, between))
        if got:
            ahead.append(got)
    ctx.setdefault("notes", {})["prefill_ahead_share"] = \
        len(ahead) / (len(decodes) - 1)
    return sum(ahead) / len(ahead) / 1e6 if ahead else None


def prefill_pad_pct(ctx: dict, args: dict):
    """100 x sum(bucket - n) / sum(bucket) over ``serve.prefill``: the
    rows of the prefill programs that were padding."""
    prefills = _prefills(ctx, args)
    if prefills is None:
        return None
    got = [(s[3]["bucket"], s[3]["n"]) for s in prefills]
    return 100.0 * sum(b - n for b, n in got) / sum(b for b, _ in got)


def innermost(events: list) -> list:
    """``[[start, end, name], ...]``, disjoint and in order: the time
    each span of one thread is the innermost open one (its own time,
    its children's taken out). ``events`` by start, the enclosing
    first; a child is cut at its parent's end."""
    out, stack, cursor = [], [], 0
    for name, start, dur, *_ in events:
        while stack and stack[-1][1] <= start:
            top, end = stack.pop()
            out.append([cursor, end, top])
            cursor = end
        if stack:
            out.append([cursor, start, stack[-1][0]])
        end = start + dur
        stack.append((name, min(end, stack[-1][1]) if stack else end))
        cursor = start
    while stack:
        top, end = stack.pop()
        out.append([cursor, end, top])
        cursor = end
    return [seg for seg in out if seg[1] > seg[0]]


def idle_split(ctx: dict):
    """The device's idle time of the traced window (the chip and the
    arithmetic of ``readers.device_idle``) by what the scheduler's
    thread was innermost in: ``{"engine", "sched", "outside"}`` in per
    cent of the window, or ``None`` without ``serve.tick`` spans. The
    three add up to ``device_idle_pct.serve`` of the same trace;
    ``sched`` (any other part of ``serve.tick``) and ``outside`` (the
    benchmark's clients, between ticks) go to ``notes``. A gap between two programs starts inside an ``engine.wait``
    and ends inside an ``engine.upload`` or ``.dispatch``, each a
    millisecond and more from the nearest scheduler time: the session's
    clock skew moves time within ``engine`` and not across."""
    if "idle_split" in ctx:
        return ctx["idle_split"]
    trace = view(ctx)
    t0, t1 = tr.window_ns(trace)
    chips = [tr.union(tr.clip(tr.line_events(p, "XLA Ops"), t0, t1))
             for p in tr.device_planes(trace)]
    # serve.submit is the client's, between two ticks: not the tick's.
    mine = [e for e in thread_spans(trace) if e[0] != "serve.submit"]
    split = None
    if chips and t1 > t0 and any(e[0] == "serve.tick" for e in mine):
        busy = min(chips, key=tr.length)
        gaps = tr.subtract(np.array([[t0, t1]], np.int64), busy)
        held: dict[str, list] = {"engine": [], "sched": []}
        for start, end, name in innermost(mine):
            held["engine" if name.startswith("engine.")
                 else "sched"].append([start, end])
        split, left = {}, tr.length(gaps)
        for key, iv in held.items():
            iv = np.array(iv, np.int64).reshape(-1, 2)
            inside = tr.length(gaps) - tr.length(tr.subtract(gaps, iv))
            split[key] = 100.0 * inside / (t1 - t0)
            left -= inside
        split["outside"] = 100.0 * left / (t1 - t0)
        ctx.setdefault("notes", {}).update(
            idle_sched_pct=split["sched"],
            idle_outside_tick_pct=split["outside"])
    ctx["idle_split"] = split
    return split


def idle_engine_pct(ctx: dict, args: dict):
    """Share of the window in which no device op runs and the
    scheduler's thread is inside an ``engine.*`` span: arguments
    uploaded and the program dispatched before it, its end noticed and
    the logits fetched after it. With ``notes``' ``idle_sched_pct`` and
    ``idle_outside_tick_pct`` it adds up to ``device_idle_pct.serve``."""
    split = idle_split(ctx)
    if split is None or not any(spans(ctx, n) for n in LAUNCH):
        return None
    return split["engine"]


def sample(trace: dict, seconds: float, skip: float = 0.0) -> dict:
    """``seconds`` of a loaded view from ``skip`` seconds into its
    window, as a small recorded form for the tests: whole events only,
    the ops of each chip merged into the intervals in which any ran
    (the readers here use nothing else of them)."""
    w0, _ = tr.window_ns(trace)
    t0 = w0 + int(skip * 1e9)
    t1 = t0 + int(seconds * 1e9)
    planes = []
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            events = [e for e in ln["events"]
                      if e[0] != tr.WINDOW and e[1] >= t0
                      and e[1] + e[2] <= t1]
            if ln["name"] == "XLA Ops":
                events = [["ops", int(s), int(e - s)]
                          for s, e in tr.union(tr.clip(events, t0, t1))]
            if any(e[0] == tr.WINDOW for e in ln["events"]):
                events.append([tr.WINDOW, t0, t1 - t0, {}])
            if events:
                lines.append({"name": ln["name"], "events": events})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


if __name__ == "__main__":
    import json

    seen = load(sys.argv[1])
    ctx_ = {"span_trace": seen}
    print(json.dumps({f.__name__: f(ctx_, {}) for f in (
        sched_self_ms, queue_wait_ms, decode_launch_ms, logits_fetch_ms,
        prefill_ahead_ms, prefill_pad_pct, idle_engine_pct)}
        | {"notes": ctx_.get("notes")}, indent=1))
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(sample(seen, float(sys.argv[3]),
                             float(sys.argv[4]) if len(sys.argv) > 4 else 0.0),
                      f, separators=(",", ":"))
