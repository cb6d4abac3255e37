"""The trace reduction on a small recorded trace (the first quarter of a
second of a traced ``serve-1b-closed32`` window on a v5e, PR 25) and on
intervals small enough to work by hand."""

import json
import os

import numpy as np
import pytest

from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "serve_v5e_sample.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def test_intervals_by_hand():
    a = np.array([[0, 10], [5, 12], [20, 30]], np.int64)
    u = tr.union(a)
    assert u.tolist() == [[0, 12], [20, 30]] and tr.length(u) == 22
    b = np.array([[2, 4], [11, 25]], np.int64)
    assert tr.subtract(u, b).tolist() == [[0, 2], [4, 11], [25, 30]]
    assert tr.subtract(u, np.zeros((0, 2), np.int64)).tolist() == u.tolist()


def test_names():
    text = "%fusion.12 = bf16[8,16]{1,0} fusion(%p), kind=kLoop"
    assert tr.op_name(text) == "fusion.12"
    assert tr.family("fusion.12") == "fusion"
    assert tr.family("all-reduce-start.3") == "all-reduce-start"


def test_recorded_busy_matches_a_plain_sweep(recorded):
    b = tr.busy(recorded)
    assert b["window_s"] == 0.25 and len(b["busy_s_per_chip"]) == 1
    t0, t1 = tr.window_ns(recorded)
    ops = tr.line_events(tr.device_planes(recorded)[0], "XLA Ops")
    covered = np.zeros(t1 - t0 + 1, bool) if t1 - t0 < 5e8 else None
    for _n, s, d in ops:
        covered[max(s, t0) - t0:min(s + d, t1) - t0] = True
    assert abs(covered.sum() / 1e9 - b["busy_s_per_chip"][0]) < 1e-6
    assert 0.2 < b["busy_s_per_chip"][0] < 0.25


def test_recorded_programs_and_breakdown(recorded):
    mods = tr.modules(recorded, "^jit_run")
    assert len(mods) == 3  # three decode ticks end inside the sample
    assert all(60e6 < d < 65e6 for _s, d in mods)
    assert tr.modules(recorded, "^jit_nothing") == []
    bd = tr.breakdown(recorded, ["tick", "clients"])
    assert len(bd["device_ops"]) == 10
    assert bd["device_ops"][0][0] == "fusion"
    assert bd["idle_gaps"][0][0] == "tick"
    idle = 0.25 - tr.busy(recorded)["busy_s_per_chip"][0]
    assert abs(sum(g[1] for g in bd["idle_gaps"]) - idle) < 1e-6
    assert tr.op_seconds(recorded, "^no_such_kernel")["count"] == 0


def test_exposed_collectives_by_hand():
    ops = [["fusion.1", 0, 40], ["all-reduce.1", 40, 20], ["fusion.2", 60, 10]]
    asyncs = [["collective-permute-start.1", 30, 50], ["copy-start.1", 0, 90]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "Async XLA Ops", "events": asyncs}]}]}
    got = tr.exposed_collectives(trace)
    # no perf_window span: the window is first op to last op, [0, 70).
    # collectives cover [30, 70) of it; compute covers [0, 40) and [60, 70)
    assert got["window_s"] == 70e-9
    assert got["per_chip"] == [{"collective_s": 40e-9, "exposed_s": 20e-9}]
