"""Communication observability plane (ISSUE 20).

The acceptance pins:

- **Parser oracle**: ``obs.comms.collective_ops`` on hand-written HLO —
  tuple-shaped fused results count every member, replica groups recover
  from explicit braces, iota (with transpose) and collective-permute
  source/target pairs, bytes are exact integers.
- **Live ledger == recount**: the gauges a metered train/serve run
  publishes equal an INDEPENDENT recount of the optimized HLO — same
  integers — at dp2, zero1, hybrid (zero1+tp2) and pp2 train shapes and
  for the paged serve prefill/decode programs (tp=2: the tp psums are
  real wire bytes).
- **Off path pinned**: no registry -> ``program_text`` is never called
  (a monkeypatched bomb proves it) and the engine caches hold BARE
  jitted programs — compiled programs unchanged by construction.
- **Precision wire**: bf16 policy halves the non-scalar gradient
  collective bytes of the AS-WRITTEN schedule (pre-optimization HLO —
  the CPU backend's optimizer folds bf16 collectives back to f32, so
  only that text shows what a bf16-honoring interconnect moves):
  fp32 == 2 * bf16 EXACTLY.
- **Host byte plane**: ``handoff_bytes_total{path=preempt}`` across a
  preempt -> adopt round trip equals the ``serve.cache.kv_row_bytes``
  oracle for the moved pages — fp32 AND int8 pools, tp=1 AND tp=2 —
  and the int8 row is >= 3x smaller at head_dim 16 (3.2x exactly).
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.data.lm import synthesize_copy
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry
from ddl_tpu.obs.cost import UnknownDeviceKind
from ddl_tpu.obs.comms import (
    CPU_NOMINAL_ICI_BW,
    ICI_BW_BY_KIND,
    collective_ops,
    fit_roofline,
    ici_bw_per_device,
    mesh_axis_partitions,
    program_text,
    publish_program_ledger,
    roofline,
)
from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig
from ddl_tpu.serve.cache import kv_row_bytes
from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer

SPEC = TINY_SPEC


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, SPEC.vocab, size=n, dtype=np.int32)


def _ds(bs, nb, seq_len):
    return synthesize_copy(num_train=nb * bs, num_test=8, seq_len=seq_len,
                           vocab=SPEC.vocab, seed=0)


def _train_cfg(**kw):
    kw.setdefault("spec", SPEC)
    kw.setdefault("epochs", 1)
    kw.setdefault("eval_every", 0)
    kw.setdefault("seed", 0)
    return SeqConfig(**kw)


# -- parser oracle (hand-written HLO) -----------------------------------------

_HLO = """\
HloModule handwritten
%ar = (f32[5882]{0}, f32[]) all-reduce(f32[5882]{0} %a, f32[] %b), replica_groups={{0,2},{1,3}}, to_apply=%sum
%rs = bf16[608]{0} reduce-scatter(bf16[4864]{0} %c), replica_groups=[2,4]<=[8], dimensions={0}
%ag = f32[2432]{0} all-gather(f32[608]{0} %d), replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}
%cp = f32[2,8,16]{2,1,0} collective-permute(f32[2,8,16]{2,1,0} %e), source_target_pairs={{0,1},{1,2},{2,0},{4,5},{5,4}}
%add.1 = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %y)
"""


def test_parser_oracle_handwritten_hlo():
    ops = collective_ops(_HLO)
    assert [o["op"] for o in ops] == [
        "all-reduce", "reduce-scatter", "all-gather", "collective-permute",
    ]
    ar, rs, ag, cp = ops
    # Tuple-shaped fused result: BOTH members count (5882 floats + the
    # scalar sibling) — a fused full-vector all-reduce can't hide.
    assert ar["bytes"] == 5882 * 4 + 4
    assert ar["max_elems"] == 5882
    assert ar["dtype"] == "f32"
    assert ar["groups"] == [[0, 2], [1, 3]]
    # iota form [2,4]<=[8]: arange(8) reshaped row-major.
    assert rs["bytes"] == 608 * 2
    assert rs["groups"] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # iota + transpose [4,2]<=[2,4]T(1,0): the strided partition.
    assert ag["bytes"] == 2432 * 4
    assert ag["groups"] == [[0, 4], [1, 5], [2, 6], [3, 7]]
    # permute pairs union into connected components.
    assert cp["bytes"] == 2 * 8 * 16 * 4
    assert sorted(cp["groups"]) == [[0, 1, 2], [4, 5]]


# Lines as the TPU compiler wrote them for the 2x2 ring LM step (tiled
# layouts, async start/done pairs), cut to the attributes the parser reads.
_TPU_HLO = """\
%collective-permute-start.1 = (f32[4,1024,8,64]{1,2,3,0:T(8,128)}, f32[4,1024,8,64]{1,2,3,0:T(8,128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%bitcast.87), channel_id=1, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
%collective-permute-done.1 = f32[4,1024,8,64]{1,2,3,0:T(8,128)} collective-permute-done(%collective-permute-start.1)
%all-gather-start.2 = (f32[1,804032]{1,0:T(1,128)}, f32[4,1,804032]{2,1,0:T(1,128)}) all-gather-start(%reshape.9), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
%all-gather-done.2 = f32[4,1,804032]{2,1,0:T(1,128)} all-gather-done(%all-gather-start.2)
%all-reduce.1 = (f32[4,804032]{1,0:T(4,128)}, f32[]{:T(128)}) all-reduce(%bitcast.13, %div.3), channel_id=2, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_172
"""


def test_parser_reads_tpu_async_collectives():
    """On the chip a ppermute is a collective-permute-start/-done pair:
    one op, the result's bytes (not operand + result + contexts), the
    start line's pairs as its groups."""
    cp, ag, ar = collective_ops(_TPU_HLO)
    assert [o["op"] for o in (cp, ag, ar)] == [
        "collective-permute", "all-gather", "all-reduce"]
    assert cp["bytes"] == 4 * 1024 * 8 * 64 * 4
    assert sorted(cp["groups"]) == [[0, 1], [2, 3]]
    assert ag["bytes"] == 4 * 804032 * 4
    assert ar["bytes"] == 4 * 804032 * 4 + 4 and ar["max_elems"] == 4 * 804032


# -- mesh-axis attribution ----------------------------------------------------

def test_mesh_axis_attribution():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "sp"))
    parts = mesh_axis_partitions(mesh)
    dp_part = frozenset(frozenset({c, c + 4}) for c in range(4))
    sp_part = frozenset((frozenset(range(4)), frozenset(range(4, 8))))
    all_part = frozenset((frozenset(range(8)),))
    assert parts[dp_part] == "dp"
    assert parts[sp_part] == "sp"
    assert parts[all_part] == "dpxsp"

    reg = MetricRegistry()
    hlo = "\n".join((
        "%a = f32[256]{0} all-reduce(f32[256]{0} %x), "
        "replica_groups={{0,4},{1,5},{2,6},{3,7}}",
        "%b = f32[64]{0} all-reduce(f32[64]{0} %y), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}",
        "%c = f32[16]{0} all-reduce(f32[16]{0} %z)",
        "%d = f32[8]{0} all-reduce(f32[8]{0} %w), "
        "replica_groups={{0,2},{1,3}}",
    ))
    led = publish_program_ledger(reg, hlo, program="probe[0]", mesh=mesh)
    assert led["by_axis"] == {
        "dp": 1024, "sp": 256, "dpxsp": 64, "unknown": 32,
    }
    assert led["total_bytes"] == 1376
    ga = reg.gauge("collective_axis_bytes")
    assert ga.value(axis="dp", program="probe[0]") == 1024
    assert ga.value(axis="unknown", program="probe[0]") == 32
    assert reg.gauge("collective_bytes_total").value(
        program="probe[0]") == 1376

    # Size-1-axis collision keeps the SMALLEST subset's label: on a
    # dp=2, tp=1 mesh an all-device op is a dp op, not dpxtp.
    mesh2 = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "tp"))
    parts2 = mesh_axis_partitions(mesh2)
    assert parts2[frozenset((frozenset({0, 1}),))] == "dp"
    assert parts2[frozenset((frozenset({0}), frozenset({1})))] == "tp"

    # No mesh: everything lands under axis="unknown".
    reg2 = MetricRegistry()
    led2 = publish_program_ledger(reg2, hlo, program="probe[1]")
    assert set(led2["by_axis"]) == {"unknown"}
    assert led2["total_bytes"] == 1376


# -- ICI bandwidth table ------------------------------------------------------

def test_ici_bw_override_table_and_fallback():
    assert ici_bw_per_device(None, 5e9) == 5e9
    with pytest.raises(ValueError):
        ici_bw_per_device(None, 0.0)
    with pytest.raises(ValueError):
        ici_bw_per_device(None, -1.0)
    # CPU falls back to the nominal anchor, silently (not an error).
    assert ici_bw_per_device(jax.devices()[0]) == CPU_NOMINAL_ICI_BW
    table = dict(ICI_BW_BY_KIND)
    v4 = types.SimpleNamespace(device_kind="TPU v4", platform="tpu")
    assert ici_bw_per_device(v4) == table["v4"]
    v5p = types.SimpleNamespace(device_kind="TPU v5p slice", platform="tpu")
    assert ici_bw_per_device(v5p) == table["v5p"]
    # An unknown ACCELERATOR is an error, never the CPU nominal — that
    # anchor would read as hopelessly comms-bound. The override wins.
    weird = types.SimpleNamespace(device_kind="frobnicator-9000",
                                  platform="gpu")
    with pytest.raises(UnknownDeviceKind, match="ici-bw"):
        ici_bw_per_device(weird)
    assert ici_bw_per_device(weird, 7e10) == 7e10


# -- the two-roofline model ---------------------------------------------------

def test_roofline_model_and_fit_recovery():
    r = roofline(1e9, 1e6, 4, 1e9, 1e8)
    assert r["compute_time_model_s"] == pytest.approx(0.25)
    assert r["comms_time_model_s"] == pytest.approx(0.01)
    assert r["step_time_model_s"] == pytest.approx(0.25)
    assert r["bound"] == "compute"
    assert r["comms_fraction"] == pytest.approx(0.01 / 0.26)
    assert roofline(1e6, 1e9, 4, 1e9, 1e8)["bound"] == "comms"

    # Synthetic rows generated by a known (peak, bw) pair: the fit must
    # recover it exactly — that's the falsification contract.
    peak, bw = 2.0e9, 5.0e7
    rows = [
        {"flops": f, "bytes": b, "measured_s": max(f / peak, b / bw)}
        for f, b in ((1e9, 1e6), (1e6, 1e9), (5e8, 2e8),
                     (2e9, 1e5), (3e7, 6e8))
    ]
    fit = fit_roofline(rows)
    assert fit is not None
    assert fit["max_rel_err"] < 1e-9
    assert fit["fitted_peak_flops"] == pytest.approx(peak, rel=1e-9)
    assert fit["fitted_bw_bytes_per_s"] == pytest.approx(bw, rel=1e-9)
    # A 1-row fit is unfalsifiable; zero/missing measurements drop.
    assert fit_roofline(rows[:1]) is None
    assert fit_roofline([{"flops": 1e9, "bytes": 1e6, "measured_s": 0.0},
                         {"flops": 1e9, "bytes": 1e6}]) is None


# -- live train ledger == independent recount ---------------------------------
#
# The recount goes through ``program_text`` IN THE TEST BODY on purpose:
# that name is the test_markers comms gate — these tests compile real
# multi-device programs, so they must be visible to the topology audit
# (the literal config tuples below are its sweep surface).

def _span_compiled(tr, p, ds, nb, bs):
    """Independent recompile of span program ``p`` exactly as the
    metered run dispatched it (metrics on -> ``health=True``)."""
    k = int(p[len("train_span["):-1])
    xs = tr.stage_batches(ds.tokens, nb, bs)
    ys = tr.stage_batches(ds.targets, nb, bs)
    ws = tr.stage_batches(ds.weights, nb, bs)
    return (tr.span_program(k, health=True)
            .lower(tr.params, tr.opt_state, xs, ys, ws, jnp.int32(0))
            .compile())


def _assert_program_ledger(reg, p, ops):
    """The published ledger for program ``p`` must be EXACTLY the
    by-hand recount's integers — total, per kind, and the axis
    attribution must partition the same total."""
    assert ops, f"{p}: no collectives in a multi-device program?"
    total = sum(o["bytes"] for o in ops)
    assert reg.gauge("collective_bytes_total").value(program=p) == total
    by_kind: dict[str, int] = {}
    for o in ops:
        by_kind[o["op"]] = by_kind.get(o["op"], 0) + o["bytes"]
    gb = reg.gauge("collective_bytes")
    for kind, want in by_kind.items():
        assert gb.value(kind=kind, program=p) == want
    ga = reg.gauge("collective_axis_bytes")
    axis_total = sum(ga.value(**ls) for ls in ga.label_sets()
                     if ls["program"] == p)
    assert axis_total == total


def _span_programs(reg):
    g = reg.gauge("collective_bytes_total")
    progs = sorted(ls["program"] for ls in g.label_sets())
    assert "eval[0]" in progs
    spans = [p for p in progs if p.startswith("train_span[")]
    assert spans
    return spans


def test_live_ledger_matches_recount_dp2_and_zero1():
    for cfg, nb, bs, seq_len in (
        (_train_cfg(batch_size=8, num_workers=1, data_parallel=2,
                    scheme="full"), 1, 8, 8),
        (_train_cfg(batch_size=8, num_workers=2, data_parallel=2,
                    scheme="ring", zero1=True), 1, 8, 16),
    ):
        ds = _ds(bs, nb, seq_len)
        reg = MetricRegistry()
        tr = SeqTrainer(cfg, ds)
        tr.train(log=lambda s: None, metrics=reg)
        for p in _span_programs(reg):
            ops = collective_ops(
                program_text(_span_compiled(tr, p, ds, nb, bs))
            )
            _assert_program_ledger(reg, p, ops)


def test_live_ledger_matches_recount_hybrid_and_pp2():
    for cfg, nb, bs, seq_len in (
        (_train_cfg(batch_size=4, num_workers=2, data_parallel=2,
                    tensor_parallel=2, scheme="ring", zero1=True),
         1, 4, 16),
        (_train_cfg(batch_size=4, num_workers=1, pipeline_parallel=2,
                    microbatches=2, scheme="full"), 1, 4, 8),
    ):
        ds = _ds(bs, nb, seq_len)
        reg = MetricRegistry()
        tr = SeqTrainer(cfg, ds)
        tr.train(log=lambda s: None, metrics=reg)
        for p in _span_programs(reg):
            ops = collective_ops(
                program_text(_span_compiled(tr, p, ds, nb, bs))
            )
            _assert_program_ledger(reg, p, ops)


# -- live serve ledger == independent recount ---------------------------------

def test_serve_paged_ledger_matches_recount():
    from ddl_tpu.serve import engine as engine_mod

    reg = MetricRegistry()
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=32, page_size=8,
                      num_pages=8, tensor_parallel=2)
    eng = InferenceEngine(cfg)
    sched = Scheduler(eng, registry=reg)
    done, _ = sched.run([Request(id=0, prompt=_prompt(6, 3),
                                 max_new_tokens=4)])
    assert done[0].status == "ok"
    g = reg.gauge("collective_bytes_total")
    progs = {ls["program"] for ls in g.label_sets()}
    assert any(p.startswith("prefill[") for p in progs)
    assert any(p.startswith("decode[") for p in progs)
    checked = 0
    for cache, kind in ((eng._prefill_fns, "prefill"),
                        (eng._decode_paged_fns, "decode")):
        for key, fn in cache.items():
            assert isinstance(fn, engine_mod._LedgeredProgram)
            if fn._compiled is None:  # built but never dispatched
                assert f"{kind}[{key}]" not in progs
                continue
            ops = collective_ops(program_text(fn._compiled))
            want = sum(o["bytes"] for o in ops)
            # tp=2: the per-block tensor-parallel psums are REAL wire
            # bytes — a zero here would mean the ledger parsed nothing.
            assert want > 0
            assert g.value(program=f"{kind}[{key}]") == want
            checked += 1
    assert checked >= 2


# -- off path: no registry, no HLO fetch, bare programs -----------------------

def test_off_path_never_fetches_hlo(monkeypatch):
    from ddl_tpu.obs import comms
    from ddl_tpu.serve import engine as engine_mod

    def _bomb(compiled):
        raise AssertionError("registry-less run fetched HLO text")

    monkeypatch.setattr(comms, "program_text", _bomb)
    # Trainer without metrics: the ledger block is never entered.
    ds = _ds(bs=8, nb=1, seq_len=8)
    cfg = _train_cfg(batch_size=8, num_workers=1, scheme="full")
    SeqTrainer(cfg, ds).train(log=lambda s: None)
    # Scheduler without a registry: no ledger hook, and the engine
    # caches hold BARE jitted programs — not _LedgeredProgram wrappers —
    # so the compiled artifacts are unchanged by construction.
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=32,
                                      page_size=8, num_pages=8))
    sched = Scheduler(eng)
    done, _ = sched.run([Request(id=0, prompt=_prompt(5, 1),
                                 max_new_tokens=3)])
    assert done[0].status == "ok"
    assert eng.ledger_hook is None
    for fn in (*eng._prefill_fns.values(),
               *eng._decode_paged_fns.values()):
        assert not isinstance(fn, engine_mod._LedgeredProgram)


# -- precision policy halves the gradient wire --------------------------------

def test_bf16_halves_gradient_wire_bytes_exactly():
    ds = _ds(bs=8, nb=1, seq_len=8)

    def wire(precision):
        cfg = _train_cfg(batch_size=8, num_workers=1, data_parallel=2,
                         scheme="full", precision=precision)
        tr = SeqTrainer(cfg, ds)
        xs = tr.stage_batches(ds.tokens, 1, 8)
        ys = tr.stage_batches(ds.targets, 1, 8)
        ws = tr.stage_batches(ds.weights, 1, 8)
        low = tr.span_program(1).lower(tr.params, tr.opt_state, xs, ys,
                                       ws, jnp.int32(0))
        # The AS-WRITTEN schedule: pre-optimization HLO. The CPU
        # backend's optimizer folds bf16 collectives back to f32
        # (converts are free host-side), so only this text shows the
        # bytes a bf16-honoring interconnect would move. Non-scalar
        # all-reduce/reduce-scatter = the gradient reductions (the
        # scalar loss/denominator psums stay fp32 under the policy).
        ops = collective_ops(low.as_text(dialect="hlo"))
        return sum(o["bytes"] for o in ops
                   if o["op"] in ("all-reduce", "reduce-scatter")
                   and o["max_elems"] > 1)

    fp32, bf16 = wire("fp32"), wire("bf16")
    assert bf16 > 0
    assert fp32 == 2 * bf16


# -- host byte plane: preempt -> adopt round trip == kv_row_bytes oracle ------

def _pin_handoff_roundtrip(tp, kv_dtype):
    reg = MetricRegistry()
    cfg = ServeConfig(spec=SPEC, slots=1, capacity=32, page_size=8,
                      num_pages=8, tensor_parallel=tp, kv_dtype=kv_dtype)
    eng = InferenceEngine(cfg)
    s = Scheduler(eng, registry=reg)
    s.begin()
    s.submit(Request(id=0, prompt=_prompt(6, 3), max_new_tokens=6))
    for _ in range(3):
        s.tick()
    pre = s.preempt(0)
    pages = int(pre.pos.shape[0])
    assert pages > 0
    oracle = pages * cfg.page_size * kv_row_bytes(SPEC, kv_dtype,
                                                  np.float32)
    assert eng.handoff_bytes(pages) == oracle
    c = reg.get("handoff_bytes_total")
    assert c is not None
    assert int(c.value(path="preempt")) == oracle
    s.adopt(pre)
    # The load side counts nothing: one round trip stays ONE count.
    assert int(c.value(path="preempt")) == oracle
    while not s.idle:
        s.tick()
    done, _ = s.collect()
    s.release()
    assert done[0].status == "ok"


@pytest.mark.parametrize("tp", [1, 2])
def test_handoff_roundtrip_bytes_oracle_fp32(tp):
    _pin_handoff_roundtrip(tp, None)


@pytest.mark.parametrize("tp", [1, 2])
def test_handoff_roundtrip_bytes_oracle_int8(tp):
    _pin_handoff_roundtrip(tp, "int8")


def test_int8_handoff_compression_ratio():
    # TINY_SPEC head_dim = 32/2 = 16: fp32 row = 2*L*H*16*4, int8 row =
    # 2*L*H*(16+4) — 3.2x exactly, comfortably over the >=3x pin.
    fp32_row = kv_row_bytes(SPEC, None, np.float32)
    int8_row = kv_row_bytes(SPEC, "int8", np.float32)
    assert fp32_row / int8_row == pytest.approx(3.2)
    assert fp32_row >= 3 * int8_row
