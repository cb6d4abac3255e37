"""From the profiler's ``.xplane.pb`` to numbers.

``load`` turns the file into a plain dict, the recorded form of which
``perf/tests`` keep a small sample; everything else works on that form
with numpy and nothing of the program.

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

What a v5e trace holds (looked at by hand, PR 25): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one event per executed HLO instruction, never overlapping on one chip,
named by the instruction's whole text) and ``Async XLA Ops`` (the
``-start``/``-done`` pairs of copies, slices and collectives, which
overlap the others); and one plane ``/host:CPU`` with a line per
thread, on the same clock. An op's name is cut to the instruction's
own name here: the text before `` = ``, without the ``%``.
"""

from __future__ import annotations

import json
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "perf_window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def op_name(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


def family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: instructions of one kind together."""
    return re.sub(r"[.\d]+$", "", name)


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` with JAX's own reader; keep the device
    lines and the host threads."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        lines = []
        for line in plane.lines:
            events = [[op_name(ev.name) if device else ev.name,
                       int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    found = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(found, key=lambda p: int(DEVICE_PLANE.match(p["name"])[1]))


def line_events(plane: dict, line: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return ln["events"]
    return []


def host_spans(trace: dict, name: str | None = None) -> list:
    """Events of the host's threads, all or those of one name, by start."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != "/host:CPU":
            continue
        for ln in plane["lines"]:
            out.extend(e for e in ln["events"] if name is None or e[0] == name)
    return sorted(out, key=lambda e: e[1])


def window_ns(trace: dict) -> tuple[int, int]:
    """The traced window: the benchmark's own ``perf_window`` span on
    the host, or from the first device op to the last where a recorded
    sample has none."""
    spans = host_spans(trace, WINDOW)
    if spans:
        return spans[0][1], spans[-1][1] + spans[-1][2]
    ops = [e for p in device_planes(trace) for e in line_events(p, "XLA Ops")]
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def clip(events, t0: int, t1: int) -> np.ndarray:
    """``[[start, end], ...]`` of the events cut to ``[t0, t1]``."""
    if not events:
        return np.zeros((0, 2), np.int64)
    a = np.array([[e[1], e[1] + e[2]] for e in events], np.int64)
    a = np.clip(a, t0, t1)
    return a[a[:, 1] > a[:, 0]]


def union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted, disjoint intervals."""
    if len(intervals) == 0:
        return intervals
    a = intervals[np.argsort(intervals[:, 0])]
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, np.int64)


def length(intervals: np.ndarray) -> int:
    return int((intervals[:, 1] - intervals[:, 0]).sum()) if len(intervals) else 0


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The parts of the disjoint intervals ``a`` that no interval of the
    disjoint ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return np.array(out, np.int64).reshape(-1, 2)


def busy(trace: dict) -> dict:
    """Per chip the seconds in which an op ran, and the window."""
    t0, t1 = window_ns(trace)
    per_chip = [length(union(clip(line_events(p, "XLA Ops"), t0, t1))) / 1e9
                for p in device_planes(trace)]
    return {"window_s": (t1 - t0) / 1e9, "busy_s_per_chip": per_chip}


def op_seconds(trace: dict, pattern: str, line: str = "XLA Ops") -> dict:
    """Summed device time and number of the ops whose name matches
    ``pattern`` (a regular expression, searched), inside the window,
    on the chip that spent most."""
    rx = re.compile(pattern)
    t0, t1 = window_ns(trace)
    best = {"seconds": 0.0, "count": 0, "durations_s": []}
    for p in device_planes(trace):
        ev = [e for e in line_events(p, line)
              if rx.search(e[0]) and e[1] >= t0 and e[1] + e[2] <= t1]
        total = sum(e[2] for e in ev) / 1e9
        if total > best["seconds"]:
            best = {"seconds": total, "count": len(ev),
                    "durations_s": [e[2] / 1e9 for e in ev]}
    return best


def modules(trace: dict, pattern: str) -> list:
    """``[start_ns, duration_ns]`` of the executed programs whose name
    matches, inside the window, in order, on the first chip."""
    rx = re.compile(pattern)
    t0, t1 = window_ns(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    ev = [e for e in line_events(planes[0], "XLA Modules")
          if rx.search(e[0]) and e[1] >= t0 and e[1] + e[2] <= t1]
    return [[e[1], e[2]] for e in sorted(ev, key=lambda e: e[1])]


def exposed_collectives(trace: dict) -> dict:
    """Per chip the seconds inside collective ops (async start-to-done
    spans and synchronous ones) in which no other op ran on that chip."""
    t0, t1 = window_ns(trace)
    out = []
    for p in device_planes(trace):
        sync = line_events(p, "XLA Ops")
        comm = [e for e in sync if COLLECTIVE.match(e[0])]
        comm += [e for e in line_events(p, "Async XLA Ops")
                 if COLLECTIVE.match(e[0])]
        compute = [e for e in sync if not COLLECTIVE.match(e[0])]
        c = union(clip(comm, t0, t1))
        out.append({"collective_s": length(c) / 1e9,
                    "exposed_s": length(subtract(
                        c, union(clip(compute, t0, t1)))) / 1e9})
    return {"window_s": (t1 - t0) / 1e9, "per_chip": out}


def breakdown(trace: dict, host_names=None, top: int = 10) -> dict:
    """The ten op families that took most device time and the ten
    longest idle gaps of the first chip, each named by the host span
    (of ``host_names``, if given) that covers most of it."""
    t0, t1 = window_ns(trace)
    planes = device_planes(trace)
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    acc: dict[str, int] = {}
    for e in line_events(planes[0], "XLA Ops"):
        if e[1] >= t0 and e[1] + e[2] <= t1:
            acc[family(e[0])] = acc.get(family(e[0]), 0) + e[2]
    ops = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    busy_iv = union(clip(line_events(planes[0], "XLA Ops"), t0, t1))
    gaps = subtract(np.array([[t0, t1]], np.int64), busy_iv)
    spans = [e for e in host_spans(trace)
             if e[0] != WINDOW and (host_names is None or e[0] in host_names)]
    by_name: dict[str, int] = {}
    for s, e in gaps:
        name, most = "no host span", 0
        for sp in spans:
            if sp[1] >= e:
                break
            cover = min(e, sp[1] + sp[2]) - max(s, sp[1])
            if cover > most:
                name, most = sp[0], cover
        by_name[name] = by_name.get(name, 0) + int(e - s)
    idle = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[n, v / 1e9] for n, v in idle]}


def sample(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of a trace's window: a small recorded form
    for the tests. Events that do not end inside it are dropped; the
    window's own span is cut to it."""
    t0, _ = window_ns(trace)
    t1 = t0 + int(seconds * 1e9)
    planes = []
    for p in trace["planes"]:
        lines = []
        for ln in p["lines"]:
            events = [e for e in ln["events"]
                      if e[0] != WINDOW and e[1] >= t0 and e[1] + e[2] <= t1]
            if any(e[0] == WINDOW for e in ln["events"]):
                events.append([WINDOW, t0, t1 - t0])
            if events:
                lines.append({"name": ln["name"], "events": events})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


if __name__ == "__main__":
    tr = load(sys.argv[1])
    print(json.dumps({"busy": busy(tr), "breakdown": breakdown(tr)}, indent=1))
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(sample(tr, float(sys.argv[3])), f)
