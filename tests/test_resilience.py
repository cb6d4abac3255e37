"""Fault-tolerant training & serving (ISSUE 6): the deterministic
fault-injection matrix.

Every recovery path the resilience layer claims is driven here by the
seeded injector (``ddl_tpu.resilience.faults``) — never by a mock:

- preemption (a REAL SIGTERM) at an arbitrary step + ``--resume auto``
  reproduces the uninterrupted run's params bit-for-bit (replicated AND
  the hybrid 2x2x2 dp x sp x tp cube);
- a NaN-injected step is SKIPPED in-graph with params unchanged (all
  four seq step bodies + the single-chip CNN step), the run still
  converges, and ``guard=False`` compiles the identical pre-change
  program;
- a corrupt/truncated latest checkpoint is verified out by
  ``find_latest_valid`` and resume proceeds from the previous retained
  save;
- a stalled serve request is evicted at its deadline with its pinned
  prefix refs released, co-resident requests bit-identical either way;
  overload sheds with a structured status.
"""

import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.data.lm import synthesize_copy, synthesize_prompts
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.resilience import (
    FaultInjector,
    FaultSpec,
    GuardMonitor,
    corrupt_checkpoint,
    parse_fault,
    truncate_checkpoint,
)
from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
from ddl_tpu.utils.checkpoint import (
    find_latest_valid,
    load_checkpoint,
    load_params,
    save_checkpoint,
    verify_checkpoint,
)

SPEC = TINY_SPEC
T = 32

quiet = lambda s: None


def _copy_ds(seed, num_train=64, num_test=16):
    return synthesize_copy(num_train=num_train, num_test=num_test,
                           seq_len=T, vocab=SPEC.vocab, seed=seed)


def _assert_trees_equal(a, b, **kw):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if kw:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- checkpoint hardening -----------------------------------------------------


def test_checkpoint_manifest_retention_and_rolling(tmp_path):
    """keep=N retains the last N step-stamped saves (rolling file =
    hardlink of the newest), every save carries a checksum manifest,
    and verify_checkpoint passes on intact files."""
    d = tmp_path / "ck"
    path = d / "ckpt.npz"
    for step in range(1, 6):
        save_checkpoint(path, {"a": np.full(4, float(step))},
                        step=step, keep=3)
    names = sorted(os.listdir(d))
    retained = [n for n in names if n.startswith("ckpt-")
                and n.endswith(".npz")]
    assert retained == [f"ckpt-{s:08d}.npz" for s in (3, 4, 5)]
    assert "ckpt.npz" in names
    for n in retained + ["ckpt.npz"]:
        assert (d / (n + ".manifest.json")).exists()
        assert verify_checkpoint(d / n)
    # Rolling file IS the newest retained save (same content).
    tree, step, _ = load_checkpoint(path, {"a": np.zeros(4)})
    assert step == 5 and tree["a"][0] == 5.0
    found = find_latest_valid(d)
    assert found is not None and found[1] == 5
    # max_step bounds the search (the guard's rollback contract).
    assert find_latest_valid(d, max_step=4)[1] == 4


def test_find_latest_valid_skips_corrupt_and_truncated(tmp_path):
    d = tmp_path / "ck"
    path = d / "ckpt.npz"
    save_checkpoint(path, {"a": np.arange(8.0)}, step=1, keep=3)
    save_checkpoint(path, {"a": np.arange(8.0) + 1}, step=2, keep=3)
    # Corrupt the LATEST (the rolling file is a hardlink of it, so both
    # names go bad together — exactly the torn-latest scenario).
    corrupt_checkpoint(path)
    assert not verify_checkpoint(path)
    assert not verify_checkpoint(d / "ckpt-00000002.npz")
    skipped = []
    found = find_latest_valid(d, log=skipped.append)
    assert found is not None and found[1] == 1
    assert any("skipping" in s for s in skipped)
    tree, step, _ = load_checkpoint(found[0], {"a": np.zeros(8)})
    assert step == 1 and tree["a"][3] == 3.0
    # Truncation of the survivor too -> nothing valid remains.
    truncate_checkpoint(found[0])
    assert find_latest_valid(d) is None


def test_checkpoint_mismatch_error_names_missing_and_unexpected(tmp_path):
    """ISSUE 6 satellite, both directions: the file lacking expected
    leaves names them path-qualified AND names the file's own
    unexpected keys."""
    path = tmp_path / "ckpt.npz"
    save_checkpoint(path, {"a": np.zeros(2), "b": np.ones(2)})
    with pytest.raises(KeyError) as ei:
        load_checkpoint(path, {"a": np.zeros(2), "c": np.zeros(2)})
    msg = str(ei.value)
    assert "['c']" in msg and "missing" in msg
    assert "['b']" in msg and "unexpected" in msg
    # Other direction: template a SUBSET of the file loads fine (extra
    # keys are simply never read — the documented contract).
    tree, _, _ = load_checkpoint(path, {"b": np.zeros(2)})
    assert tree["b"][0] == 1.0


def test_load_params_mismatch_names_keys(tmp_path):
    path = tmp_path / "ckpt.npz"
    save_checkpoint(path, {"params": {"w": np.zeros(3)}, "opt": np.zeros(1)})
    with pytest.raises(KeyError) as ei:
        load_params(path, {"w": np.zeros(3), "missing": np.zeros(2)})
    msg = str(ei.value)
    assert "missing" in msg and "['missing']" in msg
    # Matching subtree still loads from the trainer layout.
    tree, _, _ = load_params(path, {"w": np.zeros(3)})
    assert tree["w"].shape == (3,)


# -- fault specs / guard policy (host-side units) -----------------------------


def test_parse_fault_specs():
    s = parse_fault("nan_grads@3x2")
    assert (s.kind, s.step, s.count, s.once) == ("nan_grads", 3, 2, True)
    assert parse_fault("nan_grads@3x2!").once is False
    assert parse_fault("sigterm@5").step == 5
    assert parse_fault("corrupt_ckpt").kind == "corrupt_ckpt"
    assert parse_fault("stall@7").step == 7
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_fault("bogus@1")
    with pytest.raises(ValueError, match="integer"):
        parse_fault("nan_grads@x")


def test_guard_monitor_escalation_policy():
    mon = GuardMonitor(max_bad_steps=3, max_rollbacks=1)
    assert not mon.observe([0, 1, 1], first_gstep=0)  # streak of 2
    assert mon.streak_start == 1
    assert not mon.observe([0], first_gstep=3)  # streak broken
    assert mon.streak_start is None
    assert mon.observe([1, 1, 1], first_gstep=4)  # trips at 3
    assert mon.streak_start == 4
    assert mon.skipped_steps == 5
    mon.rolled_back(2)
    assert mon.consecutive == 0 and mon.rollbacks == 1
    mon.observe([1, 1, 1], first_gstep=2)
    with pytest.raises(RuntimeError, match="max_rollbacks"):
        mon.rolled_back(2)
    with pytest.raises(ValueError):
        GuardMonitor(max_bad_steps=-1)


def test_guard_monitor_trip_preserves_streak_start():
    """A healthy flag AFTER the trip inside the same span belongs to
    the abandoned (to-be-replayed) timeline — it must not reset the
    rollback bound (a None streak_start would let the rollback pick a
    checkpoint saved DURING the streak)."""
    mon = GuardMonitor(max_bad_steps=3)
    assert mon.observe([0, 0, 1, 1, 1, 0, 0, 0], first_gstep=10)
    assert mon.streak_start == 12
    # Flags past the trip were discarded unprocessed.
    assert mon.skipped_steps == 3


def test_discard_newer_prunes_abandoned_timeline(tmp_path):
    """Rollback prunes retained saves newer than the rollback step and
    re-points the rolling file at the newest survivor, so a crash
    before the replay overtakes them cannot hand --resume auto (or a
    plain --resume) a stale higher-step file."""
    from ddl_tpu.utils.checkpoint import discard_newer

    d = tmp_path / "ck"
    path = d / "ckpt.npz"
    for step in (1, 2, 3):
        save_checkpoint(path, {"a": np.full(2, float(step))},
                        step=step, keep=3)
    discard_newer(d, 1)
    names = sorted(n for n in os.listdir(d) if n.endswith(".npz"))
    assert names == ["ckpt-00000001.npz", "ckpt.npz"]
    assert find_latest_valid(d)[1] == 1
    tree, step, _ = load_checkpoint(path, {"a": np.zeros(2)})
    assert step == 1 and tree["a"][0] == 1.0
    assert verify_checkpoint(path)


# -- NaN guard: in-graph skip across every step body --------------------------


def _poisoned_span(trainer, ds, batch, *, bs=16, bn=4):
    """(program, args) for a 1-step guarded span whose batch ``batch``
    has one NaN loss weight — the direct params-unchanged pin."""
    prog = trainer.span_program(1, guard=True)
    xs = trainer.stage_batches(ds.tokens, bn, bs)
    ys = trainer.stage_batches(ds.targets, bn, bs)
    w = np.array(ds.weights, copy=True)
    w[batch * bs, 0] = np.nan
    ws = trainer.stage_batches(w, bn, bs)
    return prog, (xs, ys, ws)


def test_seq_guard_skips_nan_step_params_unchanged():
    """Acceptance (b), device half, replicated body: the poisoned step
    leaves params AND optimizer state bit-identical (identity applied
    in-graph) and raises the skip flag; the clean step updates."""
    ds = _copy_ds(8)
    tr = SeqTrainer(SeqConfig(epochs=1, eval_every=0, batch_size=16,
                              num_workers=1, scheme="full", spec=SPEC), ds)
    prog, (xs, ys, ws) = _poisoned_span(tr, ds, batch=1)
    p0 = jax.tree.map(jnp.copy, tr.params)
    o0 = jax.tree.map(jnp.copy, tr.opt_state)
    p1, o1, loss, skipped = prog(p0, o0, xs, ys, ws, jnp.int32(1))
    assert int(np.asarray(skipped)[0]) == 1
    _assert_trees_equal(tr.params, p1)
    _assert_trees_equal(tr.opt_state, o1)
    # Clean batch: flag low, params move.
    p2, o2, loss2, sk2 = prog(
        jax.tree.map(jnp.copy, tr.params),
        jax.tree.map(jnp.copy, tr.opt_state), xs, ys, ws, jnp.int32(0),
    )
    assert int(np.asarray(sk2)[0]) == 0
    assert np.isfinite(float(loss2))
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(p2))
    )
    assert moved


def test_guard_skips_in_zero1_hybrid_and_pipeline_bodies():
    """The SAME in-graph skip contract in the other three seq step
    bodies: zero1 (flat-chunk sharded Adam), the hybrid zero1 x tp cube
    body, and the pipeline schedule-scan body. One poisoned step each —
    params and optimizer state bit-unchanged, flag up."""
    ds = _copy_ds(9)
    configs = {
        "zero1": SeqConfig(epochs=1, eval_every=0, batch_size=16,
                           num_workers=2, scheme="ring", zero1=True,
                           spec=SPEC),
        "hybrid": SeqConfig(epochs=1, eval_every=0, batch_size=16,
                            num_workers=2, data_parallel=2,
                            tensor_parallel=2, scheme="ring", zero1=True,
                            spec=SPEC),
        "pipeline": SeqConfig(epochs=1, eval_every=0, batch_size=16,
                              num_workers=1, scheme="full",
                              pipeline_parallel=2, microbatches=2,
                              spec=SPEC),
    }
    for name, cfg in configs.items():
        tr = SeqTrainer(cfg, ds)
        prog, (xs, ys, ws) = _poisoned_span(tr, ds, batch=0)
        p0 = jax.tree.map(jnp.copy, tr.params)
        o0 = jax.tree.map(jnp.copy, tr.opt_state)
        p1, o1, _, skipped = prog(p0, o0, xs, ys, ws, jnp.int32(0))
        assert int(np.asarray(skipped)[0]) == 1, name
        _assert_trees_equal(tr.params, p1)
        _assert_trees_equal(tr.opt_state, o1)


def test_guard_off_compiles_identical_program():
    """Acceptance (b), program-identity half: guard=False lowers to the
    EXACT same HLO as the pre-change default (the flag is a Python
    branch), and guard=True is genuinely a different program."""
    ds = _copy_ds(8)
    tr = SeqTrainer(SeqConfig(epochs=1, eval_every=0, batch_size=16,
                              num_workers=1, scheme="full", spec=SPEC), ds)
    xs = tr.stage_batches(ds.tokens, 4, 16)
    ys = tr.stage_batches(ds.targets, 4, 16)
    ws = tr.stage_batches(ds.weights, 4, 16)
    args = (tr.params, tr.opt_state, xs, ys, ws, jnp.int32(0))
    default = tr.span_program(2).lower(*args).as_text()
    off = tr.span_program(2, guard=False).lower(*args).as_text()
    on = tr.span_program(2, guard=True).lower(*args).as_text()
    assert default == off
    assert default != on


def test_single_chip_guard_skips_and_converges(small_dataset, small_params):
    """The CNN step body honours the same contract: an injected-NaN
    batch is skipped (counted in the result), every other step trains,
    and the final state is finite."""
    from ddl_tpu.models import cnn
    from ddl_tpu.train import SingleChipTrainer, TrainConfig

    cfg = TrainConfig(epochs=1, batch_size=256, eval_every=0, seed=5,
                      conv_channels=cnn.TINY_CONV_CHANNELS,
                      fc_sizes=cnn.TINY_FC_SIZES)
    inj = FaultInjector(FaultSpec(kind="nan_grads", step=2))
    r = SingleChipTrainer(cfg, small_dataset, init=small_params).train(
        log=quiet, guard=True, fault_injector=inj
    )
    assert r.skipped_steps == 1 and r.rollbacks == 0
    for v in r.params.values():
        assert np.isfinite(np.asarray(v)).all()


def test_seq_guard_converges_with_injected_nan():
    """Acceptance (b), end to end: with the guard on, a NaN-injected
    run completes finite and lands at the clean run's loss (the skipped
    batch's contribution is the only difference)."""
    ds = _copy_ds(11)
    cfg = SeqConfig(epochs=2, eval_every=0, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC, seed=3)
    clean = SeqTrainer(cfg, ds).train(log=quiet)
    inj = FaultInjector(FaultSpec(kind="inf_grads", step=1))
    faulted = SeqTrainer(cfg, ds).train(log=quiet, guard=True,
                                        fault_injector=inj)
    # Batch 1 is poisoned on both epoch passes -> exactly 2 skips.
    assert faulted.skipped_steps == 2
    assert np.isfinite(faulted.final_loss)
    assert abs(faulted.final_loss - clean.final_loss) < 0.15 * clean.final_loss


def test_seq_guard_rollback_reseeds_to_checkpoint():
    """Escalation: K consecutive bad steps roll back to the last good
    checkpoint; the transient fault heals and the replayed data stream
    (re-seeded by step position) finishes BIT-IDENTICAL to the same
    guarded run without the fault — the strongest possible
    rollback-correctness pin: same program, same batches, same order.
    The UNGUARDED clean run steps through another program (no finite
    check, no select), and XLA on the CPU promises no bitwise equality
    across programs (every guarded run is 3e-8 from it, fault or none;
    PR 30): against it the params are held to 1e-5, the accuracy
    exactly."""
    import tempfile

    ds = _copy_ds(8)
    cfg = SeqConfig(epochs=1, eval_every=1, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC, seed=3)
    clean = SeqTrainer(cfg, ds).train(log=quiet)
    guarded = SeqTrainer(cfg, ds).train(
        log=quiet, checkpoint_dir=tempfile.mkdtemp(), checkpoint_every=1,
        max_bad_steps=2,
    )
    assert guarded.rollbacks == 0 and guarded.skipped_steps == 0
    d = tempfile.mkdtemp()
    inj = FaultInjector(FaultSpec(kind="nan_grads", step=1, count=2))
    r = SeqTrainer(cfg, ds).train(
        log=quiet, checkpoint_dir=d, checkpoint_every=1,
        max_bad_steps=2, fault_injector=inj,
    )
    assert r.rollbacks == 1 and r.skipped_steps == 2
    _assert_trees_equal(guarded.params, r.params)
    _assert_trees_equal(clean.params, r.params, rtol=0, atol=1e-5)
    assert r.final_accuracy == clean.final_accuracy


def test_guard_rollback_without_checkpoint_raises():
    ds = _copy_ds(8, num_train=32)
    cfg = SeqConfig(epochs=1, eval_every=1, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC)
    inj = FaultInjector(FaultSpec(kind="nan_grads", step=0))
    with pytest.raises(RuntimeError, match="no checkpoint_dir"):
        SeqTrainer(cfg, ds).train(log=quiet, max_bad_steps=1,
                                  fault_injector=inj)


def test_persistent_fault_exhausts_rollbacks():
    """A fault that does NOT heal (once=False — persistently bad data)
    re-trips after every rollback; the bound turns a silent livelock
    into a diagnosed failure."""
    import tempfile

    ds = _copy_ds(8, num_train=32)
    cfg = SeqConfig(epochs=1, eval_every=1, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC)
    inj = FaultInjector(FaultSpec(kind="nan_grads", step=1, once=False))
    with pytest.raises(RuntimeError, match="max_rollbacks"):
        SeqTrainer(cfg, ds).train(
            log=quiet, checkpoint_dir=tempfile.mkdtemp(),
            checkpoint_every=1, max_bad_steps=1, max_rollbacks=1,
            fault_injector=inj,
        )


# -- preemption: SIGTERM at an arbitrary step + --resume auto -----------------


def _with_cli_signal_flag():
    """The CLI's real SIGTERM/SIGINT flag handler, plus the originals
    for restoration (the handler self-resets to SIG_DFL on delivery —
    a leaked handler would kill the test process on the next signal)."""
    from ddl_tpu.cli import _install_sigterm_flag

    saved = (signal.getsignal(signal.SIGTERM),
             signal.getsignal(signal.SIGINT))
    return _install_sigterm_flag(True), saved


def _restore_signals(saved):
    signal.signal(signal.SIGTERM, saved[0])
    signal.signal(signal.SIGINT, saved[1])


def test_sigterm_resume_auto_bit_identical_replicated(tmp_path):
    """Acceptance (a), replicated: a REAL SIGTERM delivered by the
    injector once step 1 completes drains the span, writes the final
    checkpoint, and stops; --resume auto discovers it and the stitched
    run is bit-identical to the uninterrupted one."""
    ds = _copy_ds(12)
    cfg = SeqConfig(epochs=2, eval_every=2, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC, seed=4)
    golden = SeqTrainer(cfg, ds).train(log=quiet)
    d = str(tmp_path / "ck")
    term, saved = _with_cli_signal_flag()
    try:
        inj = FaultInjector(FaultSpec(kind="sigterm", step=1))
        pre = SeqTrainer(cfg, ds).train(
            log=quiet, checkpoint_dir=d, fault_injector=inj,
            should_stop=lambda: term["flag"],
        )
    finally:
        _restore_signals(saved)
    assert pre.preempted
    assert find_latest_valid(d) is not None
    resumed = SeqTrainer(cfg, ds).train(log=quiet, checkpoint_dir=d,
                                        resume="auto")
    assert 0 < resumed.resumed_from_step < 8
    assert not resumed.preempted
    _assert_trees_equal(golden.params, resumed.params)
    assert resumed.final_accuracy == golden.final_accuracy


def test_preempt_resume_auto_bit_identical_hybrid_cube(tmp_path):
    """Acceptance (a), hybrid 2x2x2: the zero1 x tp cube's sharded
    optimizer state survives preempt -> auto-resume bit-identically
    (flat dp x sp chunks and tp-local m/v round-trip the layout-free
    checkpoint form)."""
    ds = _copy_ds(23, num_train=32)
    cfg = SeqConfig(epochs=2, eval_every=1, batch_size=16, num_workers=2,
                    data_parallel=2, tensor_parallel=2, scheme="ring",
                    zero1=True, spec=SPEC, seed=13)
    golden = SeqTrainer(cfg, ds).train(log=quiet)
    d = str(tmp_path / "ck")
    polls = {"n": 0}

    def stop():
        polls["n"] += 1
        return polls["n"] > 1  # preempt after the first span

    pre = SeqTrainer(cfg, ds).train(log=quiet, checkpoint_dir=d,
                                    should_stop=stop)
    assert pre.preempted
    resumed = SeqTrainer(cfg, ds).train(log=quiet, checkpoint_dir=d,
                                        resume="auto")
    assert resumed.resumed_from_step >= 1
    _assert_trees_equal(golden.params, resumed.params)


def test_writer_tracer_flush_on_signal_exit(small_dataset, small_params,
                                            tmp_path):
    """ISSUE 6 satellite: on the signal-handler exit path (real SIGTERM
    -> drain -> preempted return -> the CLI's finally-close), the
    MetricsWriter ends with a forced final snapshot and the Tracer's
    JSONL holds the completed spans — the incident is auditable."""
    from ddl_tpu.models import cnn
    from ddl_tpu.obs import MetricRegistry, MetricsWriter
    from ddl_tpu.obs.trace import Tracer, read_jsonl
    from ddl_tpu.train import SingleChipTrainer, TrainConfig

    cfg = TrainConfig(epochs=2, batch_size=256, eval_every=2, seed=5,
                      conv_channels=cnn.TINY_CONV_CHANNELS,
                      fc_sizes=cnn.TINY_FC_SIZES)
    mpath = tmp_path / "metrics.jsonl"
    tpath = tmp_path / "trace.jsonl"
    registry = MetricRegistry()
    writer = MetricsWriter(mpath, registry, interval_s=3600)
    tracer = Tracer(tpath)
    term, saved = _with_cli_signal_flag()
    try:
        inj = FaultInjector(FaultSpec(kind="sigterm", step=1))
        r = SingleChipTrainer(cfg, small_dataset, init=small_params).train(
            log=quiet, checkpoint_dir=str(tmp_path / "ck"),
            fault_injector=inj, should_stop=lambda: term["flag"],
            metrics=registry, metrics_writer=writer, tracer=tracer,
        )
    finally:
        _restore_signals(saved)
        tracer.close()
        writer.close()
    assert r.preempted
    recs = [json.loads(line) for line in open(mpath) if line.strip()]
    assert recs[0]["record"] == "manifest"
    # interval_s=3600 means the ONLY snapshot is the forced final flush
    # on close — exactly the signal-exit guarantee under test.
    assert recs[-1]["record"] == "snapshot"
    names = {m["name"] for m in recs[-1]["metrics"]}
    assert "train_step" in names
    spans = [rec for rec in read_jsonl(tpath) if rec["type"] == "span"]
    assert any(rec["name"] == "train/span" for rec in spans)


# -- corrupt latest checkpoint: resume falls back -----------------------------


def test_corrupt_latest_checkpoint_resume_auto_falls_back(tmp_path):
    """Acceptance (c): corrupt the latest save (rolling + newest
    retained share an inode, so both go bad — the realistic torn-latest
    case); --resume auto verifies it out, resumes from the previous
    retained save, and still finishes identical to the clean run."""
    ds = _copy_ds(14)
    cfg = SeqConfig(epochs=2, eval_every=2, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC, seed=6)
    golden = SeqTrainer(cfg, ds).train(log=quiet)
    d = str(tmp_path / "ck")
    one = SeqConfig(epochs=1, eval_every=2, batch_size=16, num_workers=1,
                    scheme="full", spec=SPEC, seed=6)
    SeqTrainer(one, ds).train(log=quiet, checkpoint_dir=d,
                              checkpoint_every=1)
    latest = find_latest_valid(d)
    assert latest is not None and latest[1] == 4
    corrupt_checkpoint(os.path.join(d, "ckpt.npz"))
    fallback = find_latest_valid(d)
    assert fallback is not None and fallback[1] < 4
    logs = []
    resumed = SeqTrainer(cfg, ds).train(
        log=logs.append, checkpoint_dir=d, resume="auto"
    )
    assert resumed.resumed_from_step == fallback[1]
    assert any("skipping corrupt" in s for s in logs)
    _assert_trees_equal(golden.params, resumed.params)


# -- serve: deadlines, stall eviction, shedding -------------------------------


def _serve_engine(tp, **kw):
    from ddl_tpu.serve import InferenceEngine, ServeConfig

    return InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=64, tensor_parallel=tp, **kw
    ))


def test_stalled_request_evicted_at_deadline_releases_pins():
    """Acceptance (d): a stalled request (injector never advances its
    prefill) is evicted at its total deadline with a structured status;
    the prefix entry it pinned at admission is released (pool reusable
    afterwards) — at tp=1 AND tp=2 — and co-resident requests' tokens
    are bit-identical to a run without the stalled request."""
    from ddl_tpu.serve import Request, Scheduler

    prompts = synthesize_prompts(num=3, min_len=6, max_len=10,
                                 vocab=SPEC.vocab, seed=0)
    shared = np.concatenate([prompts[0], prompts[0][1:4]]).astype(np.int32)
    for tp in (1, 2):
        eng = _serve_engine(tp, prefix_slots=2)
        base = [
            Request(id=0, prompt=prompts[0], max_new_tokens=4),
            Request(id=2, prompt=prompts[2], max_new_tokens=4, arrival=1),
        ]
        stalled = Request(id=1, prompt=shared, max_new_tokens=4, arrival=1,
                          deadline_s=0.02)
        inj = FaultInjector(FaultSpec(kind="stall", step=1))
        done, _ = Scheduler(eng, injector=inj).run(base + [stalled])
        assert done[1].status == "deadline_exceeded"
        assert done[1].tokens == []
        assert done[0].status == "ok" and done[2].status == "ok"
        # Request 1's admission pinned the prefix entry request 0
        # registered; eviction must have released every ref.
        assert all(e.refs == 0 for e in eng.prefix._entries.values())
        # Pool reusable afterwards: a fresh request can still hit it.
        again, _ = Scheduler(eng).run(
            [Request(id=3, prompt=shared, max_new_tokens=2)]
        )
        assert again[3].status == "ok"
        # Co-resident determinism: same ids on a fresh engine WITHOUT
        # the stalled neighbour produce the same tokens bit-for-bit.
        eng2 = _serve_engine(tp, prefix_slots=2)
        done2, _ = Scheduler(eng2).run(base)
        assert done2[0].tokens == done[0].tokens
        assert done2[2].tokens == done[2].tokens


def test_serve_shed_admission_and_metrics():
    """Overload sheds at FIRST eligibility with status 'shed' (never
    occupying a slot), counts into the registry, and admitted traffic
    completes normally."""
    from ddl_tpu.obs import MetricRegistry
    from ddl_tpu.serve import Request, Scheduler

    prompts = synthesize_prompts(num=4, min_len=4, max_len=8,
                                 vocab=SPEC.vocab, seed=1)
    eng = _serve_engine(1)
    reg = MetricRegistry()
    sched = Scheduler(eng, shed_threshold=2, registry=reg)
    done, _ = sched.run([
        Request(id=i, prompt=p, max_new_tokens=2)
        for i, p in enumerate(prompts)
    ])
    statuses = [done[i].status for i in sorted(done)]
    assert statuses.count("shed") == 2
    assert statuses.count("ok") == 2
    for i in sorted(done):
        if done[i].status == "shed":
            assert done[i].admitted_step == -1 and done[i].tokens == []
    assert reg.counter("serve_shed_total").value() == 2
    assert reg.counter("serve_requests_completed_total").value() == 2


def test_scheduler_validates_resilience_config():
    """ISSUE 6 satellite: deadline/shed misconfiguration is rejected at
    CONSTRUCTION (and per-request deadlines at submit), naming the
    offending value — mirroring _validate's style."""
    from ddl_tpu.serve import Request, Scheduler

    eng = _serve_engine(1)
    with pytest.raises(ValueError, match="ttft_deadline_s.*-1"):
        Scheduler(eng, ttft_deadline_s=-1)
    with pytest.raises(ValueError, match="deadline_s.*0"):
        Scheduler(eng, deadline_s=0.0)
    with pytest.raises(ValueError, match="shed_threshold \\(1\\)"):
        Scheduler(eng, shed_threshold=1)  # below slots=2
    sched = Scheduler(eng)
    bad = Request(id=0, prompt=np.ones(4, np.int32), max_new_tokens=2,
                  ttft_deadline_s=0.0)
    with pytest.raises(ValueError, match="request 0: ttft_deadline_s"):
        sched.run([bad])
    # A stalled request with NO applicable deadline would never
    # terminate — rejected at submit.
    inj = FaultInjector(FaultSpec(kind="stall", step=0))
    with pytest.raises(ValueError, match="stall fault"):
        Scheduler(eng, injector=inj).run([
            Request(id=0, prompt=np.ones(4, np.int32), max_new_tokens=2)
        ])


def test_queued_request_expires_without_admission():
    """A queued-but-never-admitted request past its TTFT deadline
    cancels with status 'deadline_exceeded' and admitted_step == -1 (it
    held no slot, pinned nothing), while the in-flight requests finish
    normally. Both slots are taken at tick 0, so request 2 can only
    wait; tick 1's sweep (one prefill+decode dispatch later — far past
    0.1 ms of wall clock) expires it before any slot frees."""
    from ddl_tpu.serve import Request, Scheduler

    prompts = synthesize_prompts(num=3, min_len=4, max_len=8,
                                 vocab=SPEC.vocab, seed=2)
    eng = _serve_engine(1)
    done, _ = Scheduler(eng).run([
        Request(id=0, prompt=prompts[0], max_new_tokens=3),
        Request(id=1, prompt=prompts[1], max_new_tokens=3),
        Request(id=2, prompt=prompts[2], max_new_tokens=3,
                ttft_deadline_s=1e-4),
    ])
    assert done[0].status == "ok" and done[1].status == "ok"
    assert done[2].status == "deadline_exceeded"
    assert done[2].tokens == [] and done[2].admitted_step == -1
