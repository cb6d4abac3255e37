"""Structured span tracing: one span primitive, two sinks.

A :func:`span` opens a ``jax.profiler.TraceAnnotation`` — so under a
profiler session it lands on the ``/host:CPU`` plane of the same
``.xplane.pb`` as the device's ``XLA Modules`` / ``XLA Ops`` lines and
the runtime's own host events, on the host's clock (the profiler
aligns the device's with it to about a millisecond, anew each session;
``perf/clock_readers.py`` pins a serve session's to under 0.2 ms from
the runtime's events), its attributes as the event's stats —
and, where a truthy tracer is attached, also reports
``tracer.complete(name, t0, t1, **attrs)`` at exit. ``Tracer.span`` is
that same code, so ``train/span`` or ``serve.tick`` read the same in
both sinks. With no profiler session a ``TraceAnnotation`` is a no-op
(about a microsecond); with a falsy tracer no clock is read.

The tracer PROTOCOL a span (and the serve scheduler, which stamps some
events itself) relies on is three members: ``event(name, t=None,
**attrs)``, ``complete(name, t0, t1, **attrs)`` and truthiness. Anything
shaped like that may stand in for a :class:`Tracer` — the benchmark's
recorder (``perf/serve_runner.py``) does.

:class:`Tracer` is the JSONL sink: one record per line, so a trace
survives crashes mid-run (every completed span is already on disk) and
concatenates across processes. Each record carries BOTH host clocks —
``t``/``t0`` are ``time.perf_counter`` (monotonic; all intra-run math
uses these) and ``t_wall`` is ``time.time`` (correlation across
hosts/files) — plus ``pid`` and the JAX ``process_index`` so
multi-process worlds merge cleanly.

Two record types::

    {"type": "span",  "name": ..., "t0": ..., "t": ..., "dur_s": ...,
     "depth": ..., "seq": ..., "pid": ..., "process_index": ...,
     "t_wall": ..., "attrs": {...}}
    {"type": "event", "name": ..., "t": ..., "depth": ..., ...}

``Tracer.span`` spans nest (``depth`` is the span's own nesting level;
records are emitted at span END, so a child's record precedes its
parent's — order by ``t0``/``seq`` to reconstruct the tree); the bare
:func:`span` leaves ``depth`` alone and nests by containment of its
brackets. ``event`` accepts an explicit ``t`` so callers can stamp an
event with the exact ``perf_counter`` value they used for their own
derived metrics — the serve scheduler does this, which is what makes
span-derived TTFT/ITL EXACTLY equal to ``ServeStats``
(tests/test_obs.py).

``chrome_trace_events`` converts records to the Chrome/Perfetto
``trace_event`` format; ``python -m ddl_tpu.obs.trace in.jsonl out.json``
converts a file (open the result at https://ui.perfetto.dev or
chrome://tracing). ``trace_context`` opens both sinks for one
``--trace-dir`` run: the profiler session whose xplane holds every span
beside the device timeline, and the JSONL file that holds the spans
opened with a tracer plus the instant events (which the profiler has no
form for).

``NULL_TRACER`` is the disabled instance: same API, no records, and
FALSY — call sites guard clock reads with ``if tracer:`` so a disabled
run does not even pay the ``perf_counter`` calls (the off-path-unchanged
acceptance bar).
"""

from __future__ import annotations

import contextlib
import json
import os
import time

from jax.profiler import TraceAnnotation


def _process_index() -> int:
    """JAX process index, 0 when no backend is reachable. Called lazily
    at first emit / context entry — never at import — so constructing a
    tracer can never initialize a backend before the CLI configures the
    platform."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — no backend is a fine answer
        return 0


class span:
    """The span primitive: ``with span(name, tracer, **attrs) as sp``.

    Opens a ``TraceAnnotation(name, **attrs)`` for the bracket and, when
    ``tracer`` is truthy, reads ``perf_counter`` at both ends and calls
    ``tracer.complete(name, t0, t1, **attrs)`` at exit (also on an
    exception, so the span is on disk). ``sp.t0`` is the opening read;
    a caller that already reads the clock where the bracket closes (the
    scheduler's ITL stamp) assigns it to ``sp.t1`` and no second read
    is made. :meth:`set` adds attributes known only inside the bracket
    to both sinks."""

    __slots__ = ("name", "tracer", "attrs", "t0", "t1", "_ann")

    def __init__(self, name: str, tracer=None, **attrs):
        self.name = name
        self.tracer = tracer
        self.attrs = attrs
        self.t0 = self.t1 = None

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        if self.tracer:
            self.t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        tracer = self.tracer
        if tracer and self.t1 is None:
            self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if tracer:
            tracer.complete(self.name, self.t0, self.t1, **self.attrs)


class _nested_span(span):
    """A :class:`Tracer`'s own span: what is recorded inside it carries
    ``depth`` one deeper, its own record the depth it was opened at."""

    __slots__ = ()

    def __enter__(self) -> span:
        self.tracer._depth += 1
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        self.tracer._depth -= 1
        super().__exit__(*exc)


class Tracer:
    """JSONL span/event emitter. ``path=None`` keeps records in memory
    only (``self.records`` — the test/derivation surface); with a path,
    records stream to disk and are ALSO kept when ``keep=True``."""

    def __init__(self, path: str | os.PathLike | None = None, *,
                 keep: bool | None = None):
        self._path = os.fspath(path) if path is not None else None
        self._file = None
        self._keep = keep if keep is not None else self._path is None
        self.records: list[dict] = []
        self._depth = 0
        self._seq = 0
        self._pid = os.getpid()
        self._pindex: int | None = None

    def __bool__(self) -> bool:
        return True

    # -- emission ----------------------------------------------------------

    def _emit(self, rec: dict) -> None:
        if self._pindex is None:
            self._pindex = _process_index()
        rec["seq"] = self._seq
        self._seq += 1
        rec["pid"] = self._pid
        rec["process_index"] = self._pindex
        rec["t_wall"] = time.time()
        if self._keep:
            self.records.append(rec)
        if self._path is not None:
            if self._file is None:
                parent = os.path.dirname(self._path)
                if parent:
                    os.makedirs(parent, exist_ok=True)
                # "w", matching MetricsWriter: a rerun into the same
                # --trace-dir replaces the old trace — appending would
                # interleave two runs' unrelated monotonic clocks in
                # the Chrome conversion. Crash-safety is unaffected
                # (records still stream line by line).
                self._file = open(self._path, "w")
            self._file.write(json.dumps(rec) + "\n")

    def event(self, name: str, t: float | None = None, **attrs) -> None:
        """Instant event. ``t`` (``perf_counter`` seconds) defaults to
        now; pass it explicitly to stamp the event with a timestamp you
        also used elsewhere (exact-derivation contract, module doc)."""
        self._emit({
            "type": "event", "name": name,
            "t": time.perf_counter() if t is None else t,
            "depth": self._depth, "attrs": attrs,
        })

    def complete(self, name: str, t0: float, t1: float, **attrs) -> None:
        """A finished span with caller-supplied bracket timestamps."""
        self._emit({
            "type": "span", "name": name, "t0": t0, "t": t1,
            "dur_s": t1 - t0, "depth": self._depth, "attrs": attrs,
        })

    def span(self, name: str, **attrs) -> span:
        """:func:`span` reporting here, and nesting: records emitted
        inside carry ``depth`` one deeper."""
        return _nested_span(name, self, **attrs)

    # -- lifecycle ---------------------------------------------------------

    def flush(self) -> None:
        if self._file is not None:
            self._file.flush()

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class NullTracer:
    """Disabled tracer: same API, records nothing, and FALSY so call
    sites can skip even their clock reads (``if tracer: ...``)."""

    records: tuple = ()

    def __bool__(self) -> bool:
        return False

    def event(self, name: str, t: float | None = None, **attrs) -> None:
        pass

    def complete(self, name: str, t0: float, t1: float, **attrs) -> None:
        pass

    def span(self, name: str, **attrs) -> span:
        return span(name, self, **attrs)  # the profiler's sink alone

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


def host_trace_file(trace_dir: str | os.PathLike) -> str:
    """The per-process host-span JSONL path inside ``trace_dir``
    (created): ``host_trace_p<process_index>.jsonl`` — one file per
    controller, mergeable by concatenation."""
    trace_dir = os.fspath(trace_dir)
    os.makedirs(trace_dir, exist_ok=True)
    return os.path.join(trace_dir, f"host_trace_p{_process_index()}.jsonl")


@contextlib.contextmanager
def trace_context(trace_dir: str | os.PathLike | None):
    """Both sinks of :func:`span` for one bracket, in one directory
    (None = disabled: yields ``NULL_TRACER``, starts nothing): a
    ``jax.profiler`` session, whose xplane holds the spans on the
    device's clock, and the JSONL tracer it yields
    (``host_trace_p<process_index>.jsonl``)."""
    if trace_dir is None:
        yield NULL_TRACER
        return
    from ..utils.metrics import trace as profiler_trace

    trace_dir = os.fspath(trace_dir)
    with Tracer(host_trace_file(trace_dir)) as tracer, \
            profiler_trace(trace_dir):
        yield tracer


# -- Chrome/Perfetto conversion ---------------------------------------------

# Instant events that mark attribution incidents (ISSUE 11 satellite):
# rendered GLOBALLY scoped (a full-height line on the timeline, not a
# thread-local tick) under a dedicated category, and chained into flow
# arrows so the timeline shows WHERE the incident's time went — a
# guard_skip flows to its guard_rollback, a shed to the request's
# completion record, consecutive anomalies of one signal to each other.
# Fleet incidents (ISSUE 13): scale/drain/crash render full-height; a
# preempt flows to its resume (both carry req) and on to the request's
# completion record — the timeline shows the hand-off. ONE definition:
# the analyze report's fleet-incident table reads this same tuple, so
# the two surfaces cannot drift.
FLEET_EVENTS = ("scale_out", "scale_in", "drain", "preempt", "resume",
                "preempt_move", "replica_crash", "requeue", "handoff")

INCIDENT_EVENTS = frozenset({
    "anomaly", "guard_skip", "guard_rollback", "shed", "router_shed",
    "deadline_exceeded", "slo_alert",
    *FLEET_EVENTS,
})


def _flow_key(name: str, attrs: dict):
    """The identity a flow chain follows: the request for lifecycle
    incidents (a preempt chains to its resume to the completion), the
    signal for anomalies, the rule for SLO alerts, the replica for
    fleet scale/drain/crash events (a drain flows into the scale_in
    that removes the replica), one shared chain for the trainer guard
    (its skips flow into the rollback that resolves them)."""
    if "req" in attrs:
        return ("req", attrs["req"])
    if "signal" in attrs:
        return ("signal", attrs["signal"])
    if "rule" in attrs:
        return ("rule", attrs["rule"])
    if "replica" in attrs:
        return ("replica", attrs["replica"])
    if name.startswith("guard_"):
        return ("guard", "train")
    return None


def chrome_trace_events(records) -> list[dict]:
    """Tracer records -> Chrome ``trace_event`` list (``ph``="X"
    complete events for spans, "i" instants for events; timestamps in
    microseconds of the monotonic clock). Incident instants
    (:data:`INCIDENT_EVENTS`) carry ``cat="incident"``, global scope,
    and flow (``s``/``t``/``f``) chains as above. Wrap in
    ``{"traceEvents": [...]}`` or pass through :func:`convert`."""
    out = []
    chains: dict[tuple, list[dict]] = {}
    for r in records:
        base = {
            "name": r["name"],
            "pid": r.get("pid", 0),
            "tid": r.get("process_index", 0),
            "args": r.get("attrs", {}),
        }
        if r.get("type") == "span":
            out.append({**base, "ph": "X", "ts": r["t0"] * 1e6,
                        "dur": r["dur_s"] * 1e6})
            continue
        inst = {**base, "ph": "i", "ts": r["t"] * 1e6, "s": "t"}
        attrs = r.get("attrs", {})
        name = r["name"]
        incident = name in INCIDENT_EVENTS
        if incident:
            inst["s"] = "g"
            inst["cat"] = "incident"
        out.append(inst)
        # Flow chains: every incident joins its key's chain; a
        # request's `complete` instant terminates that request's chain
        # (so shed/deadline incidents point at the completion record)
        # without itself opening one.
        key = _flow_key(name, attrs)
        if key is not None and (incident or (name == "complete"
                                             and key in chains)):
            chains.setdefault(key, []).append(inst)
    for flow_id, key in enumerate(sorted(chains, key=str), start=1):
        chain = chains[key]
        if len(chain) < 2:
            continue
        for i, inst in enumerate(chain):
            ph = "s" if i == 0 else ("f" if i == len(chain) - 1 else "t")
            flow = {
                "name": f"incident:{key[0]}={key[1]}",
                "cat": "incident_flow", "ph": ph, "id": flow_id,
                "ts": inst["ts"], "pid": inst["pid"], "tid": inst["tid"],
            }
            if ph == "f":
                flow["bp"] = "e"  # bind to the enclosing slice's end
            out.append(flow)
    return sorted(out, key=lambda e: (e["ts"], e["name"], e["ph"]))


def read_jsonl(path) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def convert(src, dst) -> int:
    """JSONL trace file -> Chrome ``trace_event`` JSON file; returns the
    event count."""
    events = chrome_trace_events(read_jsonl(src))
    with open(dst, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(events)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Convert a ddl_tpu host-trace JSONL file to a "
                    "Chrome/Perfetto trace_event JSON file "
                    "(open at https://ui.perfetto.dev)"
    )
    ap.add_argument("src", help="host_trace_p*.jsonl input")
    ap.add_argument("dst", help="trace_event JSON output")
    args = ap.parse_args(argv)
    n = convert(args.src, args.dst)
    # sys.stdout.write, not print: library code routes through the
    # tracer/registry — tests/test_no_stray_prints.py enforces it, and
    # this one-line converter report is not worth an exemption.
    import sys

    sys.stdout.write(f"[obs.trace] wrote {n} trace events to {args.dst}\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
