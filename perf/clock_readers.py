"""Readers that put a traced serve window's decode programs on the
host's clock (``program_span`` metrics), named in a metric's file as
``"reader": "clock_readers.<function>"``.

The profiler aligns the device's clock with the host's anew each
session, to about a millisecond (PERF.md section 6, PR 26), and the
``engine.*`` spans that bracket a program are each a millisecond wide.
The runtime's own host events on ``/host:CPU`` are on the host's clock
beside those spans, and two of them bound a decode program on the
device (read on a v5e, PR 37): it cannot start before the runtime has
begun to put it in the device's queue (``ENQUEUE``, on one of the
runtime's own threads, after the jit call has returned), nor end after
the runtime has begun to read the flag the device sets when it is done
(``DONE``: one read a program, on the thread the flag wakes, never a
poll). Each decode call so brackets the offset ``delta`` added to
device times: ``[enqueue.start - program.start, done.start -
program.end]``. The pin is the stretch that the most calls' brackets
share (Marzullo's rule, as NTP combines its sources): where every call
agrees, ``lo = max(enqueue.start - program.start)`` and ``hi =
min(done.start - program.end)``; a call whose bracket misses that
stretch (a marker that is late or early on its own, seen once in a
driver's window, PR 37) is counted in ``notes``' ``clock_pin_dropped``
and pins nothing. ``delta`` is the stretch's middle; ``notes``'
``clock_pin_ms`` is ``[lo, hi]``. A stretch that no more than half the
calls share, or one wider than ``WIDEST_MS``, pins nothing and the two
metrics that need it read ``None``, with the reason in ``notes``. Two more
markers split the metrics: ``LAUNCH``, the jit call's entry to the
runtime, and ``LANDED``, the ids' copy to the host done.

A program is matched to the ``serve.decode`` span it overlaps most, as
``span_readers.check_clocks`` matches it; a marker is the first event of
its name on any host thread that starts inside that call's
``engine.dispatch`` .. ``engine.wait``, but for ``DONE``: the last
read that starts before ``LANDED`` does. This module loads its own view
of ``ctx["trace_path"]``: the first chip's programs, the program's
spans with their attributes, and the runtime events named in
``MARKERS``. A trace without them gives ``None`` from every reader
here: nothing raises.
"""

from __future__ import annotations

import sys

from . import harness, span_readers as sr, trace_reduce as tr

# the jit call enters the runtime
LAUNCH = "CommonPjRtLoadedExecutable::Execute"
# the program goes into the device's queue: it starts after this starts
ENQUEUE = "DoEnqueueProgram"
# the device's done-flag is read: the program has ended before this starts
DONE = "ReadSyncFlag"
# the ids' copy to the host has landed once this has ended
LANDED = "tpu::System::TransferFromDevice=>IssueEvent=>Done"
MARKERS = (LAUNCH, ENQUEUE, DONE, LANDED)
WIDEST_MS = 0.5
DECODE = "serve.decode"


def load(path: str) -> dict:
    """The view of an ``.xplane.pb`` the readers here need: the
    executed programs of each chip, and of the host the window's span,
    the program's spans with their stats and the runtime's markers."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = tr.DEVICE_PLANE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        lines = []
        for line in plane.lines:
            if device:
                if line.name != "XLA Modules":
                    continue
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events]
            else:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           {k: v for k, v in ev.stats}
                           if sr.SPAN.match(ev.name) else {}]
                          for ev in line.events
                          if ev.name == tr.WINDOW or ev.name in MARKERS
                          or sr.SPAN.match(ev.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _view(ctx: dict) -> dict:
    """``span_readers``' context over this module's view, loaded once a
    run."""
    if "clock_trace" not in ctx:
        ctx["clock_trace"] = load(ctx["trace_path"])
    return {"span_trace": ctx["clock_trace"]}


def _first(events: list, lo: int, hi: int):
    """The first of ``events`` (by start) that starts in ``[lo, hi]``."""
    return next((e for e in events if lo <= e[1] <= hi), None)


def _last(events: list, lo: int, hi: int):
    """The last of ``events`` (by start) that starts in ``[lo, hi]``."""
    return next((e for e in reversed(events) if lo <= e[1] <= hi), None)


def calls(ctx: dict) -> list:
    """The window's decode calls that have a program, both engine spans
    and every marker: ``{"program", "dispatch", "wait"}`` and a key a
    marker, each ``[start_ns, end_ns]`` on its own clock."""
    if "clock_calls" in ctx:
        return ctx["clock_calls"]
    view = _view(ctx)
    decodes = sr.spans(view, DECODE)
    inner = {n: sr.spans(view, f"engine.{n}", kind="decode")
             for n in ("dispatch", "wait")}
    marks = {m: sr.spans(view, m) for m in MARKERS}
    out = []
    for p in sr.programs(view):
        if p["kind"] != "decode":
            continue
        start, end = p["start"], p["start"] + p["duration"]
        shared, host = max(((min(end, d[1] + d[2]) - max(start, d[1]), d)
                            for d in decodes), key=lambda x: x[0],
                           default=(0, None))
        if shared <= 0:
            continue
        got = {k: sr._inside(v, host) for k, v in inner.items()}
        if not got["dispatch"] or not got["wait"]:
            continue
        lo, hi = got["dispatch"][0][1], got["wait"][-1][1] + got["wait"][-1][2]
        found = {m: _first(marks[m], lo, hi) for m in MARKERS}
        if found[LANDED]:
            # the flag read that the ids' copy follows, not an earlier one
            found[DONE] = _last(marks[DONE], lo, found[LANDED][1])
        if any(v is None for v in found.values()):
            continue
        out.append({"program": [start, end]}
                   | {k: [v[0][1], v[0][1] + v[0][2]] for k, v in got.items()}
                   | {m: [e[1], e[1] + e[2]] for m, e in found.items()})
    ctx["clock_calls"] = out
    return out


def agreed(brackets: list):
    """How many of ``brackets`` (``[lo, hi]`` each) share one stretch at
    most, and the first such stretch (``None`` where none is
    consistent): Marzullo's sweep over their edges, each bracket
    closed."""
    edges = sorted([(lo, -1) for lo, hi in brackets if lo <= hi]
                   + [(hi, 1) for lo, hi in brackets if lo <= hi])
    best, depth, out = 0, 0, None
    for k, (x, edge) in enumerate(edges):
        depth -= edge
        if depth > best:
            best, out = depth, [x, edges[k + 1][0]]
    return best, out


def pin(ctx: dict):
    """``delta`` in ns, added to device times to put them on the host's
    clock, or ``None`` (the reason in ``notes``' ``clock_pin_refused``).
    ``notes``: ``clock_pin_ms`` ``[lo, hi]``, ``clock_pin_calls``,
    ``clock_pin_dropped``: the calls whose own bracket misses the pin,
    and ``clock_pin_outside``: the pinned programs that start before
    their ``engine.dispatch`` or end after their ``engine.wait``."""
    if "clock_pin" in ctx:
        return ctx["clock_pin"]
    got, delta = calls(ctx), None
    notes = ctx.setdefault("notes", {}) if got else {}
    if got:
        share, stretch = agreed([[c[ENQUEUE][0] - c["program"][0],
                               c[DONE][0] - c["program"][1]] for c in got])
        notes["clock_pin_calls"] = len(got)
        notes["clock_pin_dropped"] = len(got) - share
        if 2 * share <= len(got):
            notes["clock_pin_refused"] = \
                f"markers disagree: {share} of {len(got)} calls share a bracket"
        else:
            lo, hi = stretch
            notes["clock_pin_ms"] = [lo / 1e6, hi / 1e6]
            if hi - lo > WIDEST_MS * 1e6:
                notes["clock_pin_refused"] = \
                    f"bracket {(hi - lo) / 1e6:.3f} ms wide, over {WIDEST_MS}"
            else:
                delta = (lo + hi) / 2
                notes["clock_pin_outside"] = sum(
                    c["program"][0] + delta < c["dispatch"][0]
                    or c["program"][1] + delta > c["wait"][1] for c in got)
    ctx["clock_pin"] = delta
    return delta


def wait_tail_ms(ctx: dict, args: dict):
    """Median over the decode calls of ``engine.wait``'s end less the
    pinned program's end: how late the host learns. ``notes``: the
    median split where the ids' copy has landed (``LANDED``), device
    end to ids on the host, then the host's own after that."""
    delta = pin(ctx)
    if delta is None:
        return None
    got = calls(ctx)
    ends = [c["program"][1] + delta for c in got]
    ctx["notes"]["wait_tail_split_ms"] = [
        sr._median_ms([c[LANDED][1] - e for c, e in zip(got, ends)]),
        sr._median_ms([c["wait"][1] - c[LANDED][1] for c in got])]
    return sr._median_ms([c["wait"][1] - e for c, e in zip(got, ends)])


def start_lag_ms(ctx: dict, args: dict):
    """Median over the decode calls of the pinned program's start less
    ``engine.dispatch``'s start: how late the device starts. ``notes``:
    the median split at the launch (``LAUNCH``), the jit call's Python,
    then the launch and the arguments landing."""
    delta = pin(ctx)
    if delta is None:
        return None
    got = calls(ctx)
    starts = [c["program"][0] + delta for c in got]
    ctx["notes"]["start_lag_split_ms"] = [
        sr._median_ms([c[LAUNCH][0] - c["dispatch"][0] for c in got]),
        sr._median_ms([s - c[LAUNCH][0] for c, s in zip(got, starts)])]
    return sr._median_ms([s - c["dispatch"][0] for c, s in zip(got, starts)])


def h2d_ms(ctx: dict, args: dict):
    """Median ``engine.h2d`` of a decode call: every host-to-device
    transfer of its arguments. ``notes``: the medians of its ``arrays``
    and ``bytes``."""
    got = sr.spans(_view(ctx), "engine.h2d", kind="decode")
    if not got:
        return None
    ctx.setdefault("notes", {}).update(
        h2d_arrays=harness.median([s[3]["arrays"] for s in got]),
        h2d_bytes=harness.median([s[3]["bytes"] for s in got]))
    return sr._median_ms([s[2] for s in got])


if __name__ == "__main__":
    import json

    seen = load(sys.argv[1])
    ctx_ = {"clock_trace": seen}
    print(json.dumps({f.__name__: f(ctx_, {}) for f in (
        wait_tail_ms, start_lag_ms, h2d_ms)}
        | {"notes": ctx_.get("notes")}, indent=1))
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(sr.sample(seen, float(sys.argv[3]),
                                float(sys.argv[4]) if len(sys.argv) > 4
                                else 0.0),
                      f, separators=(",", ":"))
