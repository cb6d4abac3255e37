"""The serve path's spans (ISSUE 26): one primitive, two sinks.

A short paged ``Scheduler`` run of each family, three ways — tracing off, a JSONL
``Tracer`` under a ``jax.profiler`` session, and a tracer shaped like
the benchmark's recorder (``event`` / ``complete`` / truthiness, nothing
else). The profiler's xplane must hold every span of the table with its
attributes and the stated nesting, the JSONL the scheduler's spans by
name and count (the engine's have no tracer: the profiler alone), the
recorder-shaped tracer the legacy ``prefill_chunk`` / ``decode_tick``
spans unchanged, and the served tokens the same bits in every run.
"""

import collections
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.models import hybrid
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs.trace import NULL_TRACER, Tracer, span
from ddl_tpu.serve import (InferenceEngine, Request, Scheduler, ServeConfig,
                           engine_cls)
from ddl_tpu.serve import engine as engine_mod
from perf import sala_weights as sw
from perf.serve_sala_runner import spec_of

SPEC = TINY_SPEC
PAGED = ServeConfig(spec=SPEC, slots=2, capacity=64, page_size=8,
                    num_pages=16)
FAMILIES = {"dense": PAGED,
            "hybrid": dataclasses.replace(PAGED, spec=hybrid.HybridSpec())}

# span -> the attributes PERF.md's table gives it: each has a reader
# in perf/span_readers.py, and no span carries any other
SPANS = {
    "serve.submit": {"req"},
    "serve.tick": set(),
    "serve.prefill": {"req", "n", "bucket"},
    "serve.decode": {"pages"},
    "engine.upload": {"kind"},
    "engine.h2d": {"kind", "arrays", "bytes"},
    "engine.dispatch": {"kind"},
    "engine.wait": {"kind"},
    "engine.fetch_logits": {"kind"},
}
# what the second family's programs count, beside (PR 28: PERF.md's table)
COUNTERS = {"serve.prefill": {"moe_assigned"},
            "serve.decode": {"moe_assigned", "moe_touched", "win_pages"}}


def attrs_of(runs, name):
    """The attributes ``name`` carries in this run's family."""
    extra = COUNTERS.get(name, set()) if runs["family"] == "hybrid" else set()
    return SPANS[name] | extra


# span -> the spans one of which must contain it on the thread
PARENTS = {
    "serve.prefill": ("serve.tick",),
    "serve.decode": ("serve.tick",),
    "engine.upload": ("serve.prefill", "serve.decode"),
    "engine.h2d": ("engine.upload",),
    "engine.dispatch": ("serve.prefill", "serve.decode"),
    "engine.wait": ("serve.prefill", "serve.decode"),
    "engine.fetch_logits": ("serve.prefill", "serve.decode"),
}


class RecorderShaped:
    """The benchmark's recorder by its calling convention: ``event``,
    ``complete`` and truthiness. Any other attribute the scheduler or
    the span primitive asked for would raise here."""

    __slots__ = ("events", "spans")

    def __init__(self):
        self.events, self.spans = [], []

    def __bool__(self):
        return True

    def event(self, name, t=None, **attrs):
        self.events.append((name, t, attrs))

    def complete(self, name, t0, t1, **attrs):
        self.spans.append((name, t0, t1, attrs))


def _requests(vocab=SPEC.vocab):
    rng = np.random.default_rng(3)
    lengths = (5, 12, 9, 20)  # buckets 8, 16, 16, 32
    return [Request(id=i, prompt=rng.integers(1, vocab, size=n,
                                              dtype=np.int32),
                    max_new_tokens=4 + i, arrival=i)
            for i, n in enumerate(lengths)]


def _serve(tracer, cfg=PAGED):
    done, _ = Scheduler(engine_cls(cfg.spec)(cfg), tracer=tracer).run(
        _requests(cfg.spec.vocab))
    return {i: list(c.tokens) for i, c in done.items()}


def _xplane_events(path):
    """``(name, start, end, attrs)`` of every event of the host plane,
    and the ``hlo_module`` names its XLA op events carry."""
    from jax.profiler import ProfileData

    events, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
                if ev.name in SPANS:
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns, stats))
    return events, modules


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def runs(request, tmp_path_factory):
    cfg = FAMILIES[request.param]
    out = {"family": request.param}
    out["off"] = _serve(None, cfg)
    rec = RecorderShaped()
    out["recorder"] = _serve(rec, cfg)
    out["rec"] = rec
    # The JSONL tracer under a profiler session. The engine compiles
    # its programs inside it: that only adds events of other names.
    tdir = str(tmp_path_factory.mktemp("prof"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tracer = Tracer()
    jax.profiler.start_trace(tdir, profiler_options=options)
    try:
        out["profiler"] = _serve(tracer, cfg)
    finally:
        jax.profiler.stop_trace()
    out["records"] = tracer.records
    files = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the profiler session left no xplane"
    out["xplane"], out["modules"] = _xplane_events(files[0])
    return out


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_reaches_the_xplane_with_its_attrs(runs, name):
    got = [e for e in runs["xplane"] if e[0] == name]
    assert got, f"no {name} event on /host:CPU"
    for _n, _s, _e, attrs in got:
        assert attrs_of(runs, name) == set(attrs), (name, attrs)
    if name.startswith("engine."):
        assert {a["kind"] for *_x, a in got} == {"prefill", "decode"}


@pytest.mark.parametrize("child", sorted(PARENTS))
def test_span_nests_by_containment(runs, child):
    parents = [e for e in runs["xplane"] if e[0] in PARENTS[child]]
    calls = [e for e in runs["xplane"]
             if e[0] in ("serve.prefill", "serve.decode")]
    for _n, s, e, attrs in (x for x in runs["xplane"] if x[0] == child):
        inside = [p for p in parents if p[1] <= s and e <= p[2]]
        assert len(inside) == 1, (child, s, e)
        if child.startswith("engine."):
            call = [p for p in calls if p[1] <= s and e <= p[2]]
            kind = call[0][0].split(".")[1]  # serve.prefill -> prefill
            assert attrs["kind"] == kind


@pytest.mark.parametrize("name", sorted(SPANS))
def test_jsonl_and_xplane_hold_the_same_spans(runs, name):
    jsonl = [r for r in runs["records"]
             if r["type"] == "span" and r["name"] == name]
    xplane = [e for e in runs["xplane"] if e[0] == name]
    if name.startswith("engine."):
        # no tracer attached: the profiler's sink alone
        assert not jsonl and xplane
        return
    assert len(jsonl) == len(xplane) > 0
    order = sorted(jsonl, key=lambda r: (r["t0"], r["seq"]))
    for rec, (_n, _s, _e, attrs) in zip(order,
                                        sorted(xplane, key=lambda e: e[1])):
        assert rec["attrs"] == attrs and attrs_of(runs, name) == set(attrs)


@pytest.mark.parametrize("how", ["recorder", "profiler"])
def test_served_tokens_are_the_same_bits(runs, how):
    assert runs[how] == runs["off"]
    assert sorted(runs["off"]) == [0, 1, 2, 3]


@pytest.mark.parametrize("legacy,new,keys", [
    ("prefill_chunk", "serve.prefill", {"req", "slot", "base", "n"}),
    ("decode_tick", "serve.decode",
     {"step", "n_active", "chained", "reqs"}),
])
def test_recorder_shaped_tracer_sees_the_legacy_spans_unchanged(
        runs, legacy, new, keys):
    """Names and attrs as before this issue, and the bracket's clock
    reads shared with the new span of the same bracket."""
    rec = runs["rec"]
    old = [s for s in rec.spans if s[0] == legacy]
    assert old and all(set(s[3]) == keys for s in old)
    ref = [r for r in runs["records"]
           if r["type"] == "span" and r["name"] == legacy]
    assert [s[3] for s in old] == [r["attrs"] for r in ref]
    fresh = [s for s in rec.spans if s[0] == new]
    assert [(s[1], s[2]) for s in old] == [(s[1], s[2]) for s in fresh]
    # the lifecycle events are the ones the benchmark's recorder reads
    names = {e[0] for e in rec.events}
    assert {"submit", "eligible", "admit", "first_token",
            "complete"} <= names


def test_legacy_stamps_keep_their_exact_values(runs):
    """``submit`` is stamped with the ``serve.submit`` span's opening
    read; ``decode_tick`` ends on the ITL clock's read."""
    rec = runs["rec"]
    submits = {e[2]["req"]: e[1] for e in rec.events if e[0] == "submit"}
    spans = {s[3]["req"]: s[1] for s in rec.spans if s[0] == "serve.submit"}
    assert submits == spans and len(submits) == 4
    ticks = [s for s in rec.spans if s[0] == "decode_tick"]
    assert all(t0 < t1 for _n, t0, t1, _a in ticks)


def test_programs_are_named_by_kind_and_bucket(runs):
    progs = {m for m in runs["modules"] if m.startswith("jit_run")}
    assert progs and all(
        re.match(r"^jit_run_(prefill_b|decode_p)\d+$", m) for m in progs)
    buckets = {a["bucket"] for n, _s, _e, a in runs["xplane"]
               if n == "serve.prefill"}
    pages = {a["pages"] for n, _s, _e, a in runs["xplane"]
             if n == "serve.decode"}
    assert buckets == {8, 16, 32}
    assert progs == {f"jit_run_prefill_b{b}" for b in buckets} \
        | {f"jit_run_decode_p{p}" for p in pages}


def test_h2d_counts_what_it_uploads(runs):
    """``engine.h2d`` makes one transfer a call (``arrays`` 1) of the
    packed buffer (``bytes``): a decode call's four ``[slots]`` vectors
    and each page group's table, a prefill's ``[1, bucket]`` tokens,
    three scalars and each group's row of its table, 4 B a value."""
    cfg = FAMILIES[runs["family"]]
    ring = getattr(engine_cls(cfg.spec)(cfg), "ring", 0)
    tables = cfg.capacity // cfg.page_size + ring  # a row of each group
    calls = [e for e in runs["xplane"]
             if e[0] in ("serve.prefill", "serve.decode")]
    got = [e for e in runs["xplane"] if e[0] == "engine.h2d"]
    assert {a["kind"] for *_x, a in got} == {"prefill", "decode"}
    for _n, s, e, a in got:
        call = next(c[3] for c in calls if c[1] <= s and e <= c[2])
        if a["kind"] == "decode":
            want = cfg.slots * 4 * (4 + call["pages"] + ring)
        else:
            want = 4 * (call["bucket"] + 3 + tables)
        assert (a["arrays"], a["bytes"]) == (1, want), (a, call)


# What each program's ``run`` received before the uploads moved into
# ``engine.h2d`` and were packed, as recorded then: a prefill and a
# decode, each argument ``dtype[shape]``, ``None`` where it takes none.
_I, _M = "int32[]", "bool"
STATE_SPEC = spec_of(sw.SalaSizes(
    name="toy", vocab=64, d_model=32, num_heads=4, head_dim=8, kv_heads=2,
    d_ff=64, mixers=(sw.SPARSE, sw.LINEAR, sw.LINEAR, sw.SPARSE), eps=1e-6,
    rope_base=10_000.0, scale_emb=12.0, scale_depth=1.4, depth=32,
    dim_model_base=16, kernel_size=4, kernel_stride=2, block_size=4, topk=4,
    init_blocks=1, window_size=8, dense_len=16))
HYBRID3 = dict(slots=3, capacity=64, page_size=4)
RECEIVED = {
    "dense-paged": (PAGED, ["int32[1,8]", _I, _I, "int32[1,8]", _I],
                    ["int32[2]", "int32[2]", "int32[2]", "bool[2]",
                     "int32[2,1]"]),
    "dense-contiguous": (
        dataclasses.replace(PAGED, page_size=0, num_pages=0),
        ["int32[1,8]", _I, _I, _I, _I],
        ["int32[2]", "int32[2]", "int32[2]", "bool[2]"]),
    "hybrid-window": (
        ServeConfig(spec=hybrid.HybridSpec(), num_pages=40, **HYBRID3),
        ["int32[1,8]", _I, _I, "int32[1,16]", "int32[1,3]", None, _I],
        ["int32[3]", "int32[3]", "int32[3]", "bool[3]", "int32[3,2]",
         "int32[3,3]"]),
    "hybrid-state": (
        ServeConfig(spec=STATE_SPEC, num_pages=48, **HYBRID3),
        ["int32[1,8]", _I, _I, "int32[1,16]", None, _I, _I],
        ["int32[3]", "int32[3]", "int32[3]", "bool[3]", "int32[3,2]",
         None]),
}


def _spy_calls(monkeypatch, eng):
    """Record what each program call receives beside the params and the
    cache (``dtype[shape]``), and what its ``run`` receives once unpacked
    (at the program's trace, ``None`` where it takes no argument)."""
    calls, unpacked = [], []
    call, unpack = eng._call, engine_mod.unpack_args

    def spy_call(kind, fn, packed, want_logits):
        calls.append(f"{packed.dtype}[{','.join(map(str, packed.shape))}]"
                     if isinstance(packed, jax.Array) and not packed.weak_type
                     else repr(packed))
        return call(kind, fn, packed, want_logits)

    def spy_unpack(packed, layout):
        args = unpack(packed, layout)
        unpacked.append([None if a is None else
                         f"{a.dtype}[{','.join(map(str, a.shape))}]"
                         for a in args])
        return args

    monkeypatch.setattr(eng, "_call", spy_call)
    monkeypatch.setattr(engine_mod, "unpack_args", spy_unpack)
    return calls, unpacked


def _prefill_prefill_decode(eng):
    eng.prefill(np.arange(1, 7, dtype=np.int32), slot=0, request_id=3)
    eng.prefill(np.arange(1, 5, dtype=np.int32), slot=1, request_id=4)
    slots = eng.config.slots
    lengths, active = np.zeros(slots, np.int32), np.zeros(slots, bool)
    lengths[:2], active[:2] = (6, 4), True
    eng.decode(np.zeros(slots, np.int32), lengths, np.arange(slots), active)


@pytest.mark.parametrize("case", sorted(RECEIVED))
def test_programs_receive_what_they_did_before_h2d(case, monkeypatch):
    """Each call hands its program ONE int32 vector, not weakly typed,
    of as many values as the arguments hold; unpacked, ``run`` receives
    the same arguments as before, count, dtypes and shapes, ``None``
    where it took none (each program traced once: two prefills of one
    bucket, then a decode)."""
    cfg, prefill, decode = RECEIVED[case]
    eng = engine_cls(cfg.spec)(cfg)
    calls, unpacked = _spy_calls(monkeypatch, eng)
    _prefill_prefill_decode(eng)
    assert unpacked == [prefill, decode]

    def size(arg):  # "int32[1,8]" -> 8
        dims = arg[arg.index("[") + 1:-1]
        return int(np.prod([int(d) for d in dims.split(",") if d]))

    sizes = [sum(size(a) for a in args if a is not None)
             for args in (prefill, prefill, decode)]
    assert calls == [f"int32[{n}]" for n in sizes]


@pytest.mark.parametrize("case", sorted(RECEIVED))
def test_each_call_makes_one_transfer(case, monkeypatch):
    """A prefill and a decode of a warm engine put ONE array on the
    device each: ``jax.device_put`` and ``jnp.asarray`` counted."""
    cfg = RECEIVED[case][0]
    eng = engine_cls(cfg.spec)(cfg)
    _prefill_prefill_decode(eng)  # every program built before counting
    made = []
    for mod, name in ((jax, "device_put"), (jnp, "asarray")):
        real = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda *a, _r=real, **k: (
            made.append(1), _r(*a, **k))[1])
    for _ in range(2):
        before = len(made)
        _prefill_prefill_decode(eng)
        assert len(made) - before == 3


@pytest.mark.parametrize("case", ["dense-paged", "hybrid-state"])
def test_packed_programs_serve_the_ids_of_per_argument_calls(
        case, monkeypatch):
    """A short ``Scheduler.run`` gives the same ids when each program's
    unchanged ``run`` takes its arguments one transfer each, as before
    packing: the packed path changes no arithmetic."""
    cfg = dataclasses.replace(RECEIVED[case][0], prefill_chunk=8)
    packed = _serve(None, cfg)

    def per_argument(kind, values):
        return tuple(v if v is None
                     else jnp.asarray(v) if isinstance(v, np.ndarray)
                     else jnp.int32(v) for v in values)

    call = InferenceEngine._call
    monkeypatch.setattr(engine_mod, "_takes_packed", lambda run, layout: run)
    monkeypatch.setattr(InferenceEngine, "_h2d", staticmethod(per_argument))
    monkeypatch.setattr(InferenceEngine, "_call", lambda self, kind, fn, args,
                        want: call(self, kind, lambda p, c, a: fn(p, c, *a),
                                   args, want))
    assert _serve(None, cfg) == packed
    assert sum(map(len, packed.values())) == 4 + 5 + 6 + 7


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_program_names_of_both_layouts(layout):
    cfg = PAGED if layout == "paged" else dataclasses.replace(
        PAGED, page_size=0, num_pages=0)
    eng = InferenceEngine(cfg)
    if layout == "paged":
        names = [eng._prefill_paged_fn(16).__name__,
                 eng._decode_paged(4).__name__]
        assert names == ["run_prefill_b16", "run_decode_p4"]
    else:
        names = [eng._prefill_fn(16).__name__, eng._decode().__name__]
        assert names == ["run_prefill_c16", "run_decode_c"]
    assert all(re.match(r"^jit_run_(prefill|decode)_", f"jit_{n}")
               for n in names)


def test_speculative_path_carries_the_same_spans():
    cfg = dataclasses.replace(PAGED, slots=4, num_pages=24, speculate_k=4)
    tracer = Tracer()
    eng = InferenceEngine(cfg)
    Scheduler(eng, tracer=tracer).run(
        [Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                 max_new_tokens=16)])
    count = collections.Counter(r["name"] for r in tracer.records
                                if r["type"] == "span")
    assert count["decode_tick"] == count["serve.decode"] > 0
    assert count["prefill_chunk"] == count["serve.prefill"] == 1
    lanes = [r["attrs"]["spec_lanes"] for r in tracer.records
             if r["name"] == "decode_tick"]
    assert any(lanes), "no draft lane rode: not the speculative path"
    ends = {name: [(r["t0"], r["t"]) for r in tracer.records
                   if r["name"] == name]
            for name in ("decode_tick", "serve.decode")}
    assert ends["decode_tick"] == ends["serve.decode"]
    assert {r["attrs"]["pages"] for r in tracer.records
            if r["name"] == "serve.decode"} <= {1, 2, 4}


def test_held_prefill_role_ticks_without_device_spans():
    """A prefill-role scheduler holds its first-token slot: the held
    ticks make no device call and carry ``serve.tick`` alone."""
    tracer = Tracer()
    sched = Scheduler(InferenceEngine(PAGED), tracer=tracer, role="prefill")
    sched.begin()
    try:
        sched.submit(Request(id=0, prompt=np.arange(1, 6, dtype=np.int32),
                             max_new_tokens=4))
        sched.tick()  # admits and prefills
        mark = len(tracer.records)
        sched.tick()  # holds
        sched.tick()
    finally:
        sched.release()
    held = collections.Counter(r["name"] for r in tracer.records[mark:])
    assert held == {"serve.tick": 2}
    first = collections.Counter(r["name"] for r in tracer.records[:mark]
                                if r["type"] == "span")
    assert first["serve.prefill"] == first["serve.tick"] == 1
    assert "serve.decode" not in first


def test_warmup_reports_no_span():
    tracer = Tracer()
    eng = InferenceEngine(PAGED)
    sched = Scheduler(eng, tracer=tracer)
    sched.warmup(_requests())
    assert not tracer.records


def test_span_reads_no_clock_without_a_tracer():
    for tracer in (None, NULL_TRACER):
        with span("x", tracer, a=1) as sp:
            sp.set(b=2)
        assert sp.t0 is None and sp.t1 is None
    with NULL_TRACER.span("y", a=1) as sp:
        pass
    assert sp.t0 is None and NULL_TRACER.records == ()


def test_span_reports_a_given_end_and_survives_a_raise():
    rec = RecorderShaped()
    with span("a", rec, k=1) as sp:
        sp.t1 = sp.t0 + 1.0
        sp.set(m=2)
    assert rec.spans == [("a", sp.t0, sp.t0 + 1.0, {"k": 1, "m": 2})]
    with pytest.raises(KeyError):
        with span("b", rec):
            raise KeyError("inside")
    assert [s[0] for s in rec.spans] == ["a", "b"]


def test_tracer_span_nests_and_bare_span_does_not():
    tr = Tracer()
    with tr.span("outer"):
        with span("bare", tr):
            tr.event("e")
        with tr.span("inner"):
            pass
    depth = {r["name"]: r["depth"] for r in tr.records}
    assert depth == {"e": 1, "bare": 1, "inner": 1, "outer": 0}
