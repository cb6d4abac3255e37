"""``correct`` has to come out false when the timed path is broken.

Each test skips the harness's look for a chip (``rehearse=True``: toy
sizes on the CPU, the cell's own files, runner, comparison and limits)
and drives the rest of a run. The faults are planted underneath the
runner, in the program's own entry points. The control (the reference
in float8 put in the program's place) has to fail the same limits.
"""

import numpy as np
import pytest

from perf import compare, harness, rehearsal, run, serve_runner, train_runner

SEED = 2_345_678_901  # above 2**31, as the driver's are


def test_train_cell_is_correct_untouched():
    res = run.run_cell("train-410m-2k", SEED, 1.0, False, rehearse=True)
    assert res["correct"], res["checked"]
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["checked"]["compiles_in_window"]["value"] == 0


def _break_span(monkeypatch, wrap):
    from ddl_tpu.strategies.seq import SeqTrainer

    real = SeqTrainer.span_program
    monkeypatch.setattr(SeqTrainer, "span_program",
                        lambda self, k, **kw: wrap(real(self, k, **kw)))


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def broken(params, opt, xs, ys, ws, first):
            copy = lambda t: jax.tree.map(jnp.copy, t)
            loss = step(copy(params), copy(opt), xs, ys, ws, first)[2]
            return params, opt, loss
        return broken

    _break_span(monkeypatch, wrap)
    res = run.run_cell("train-410m-2k", SEED, 1.0, False, rehearse=True)
    assert not res["correct"]
    assert res["checked"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def broken(params, opt, xs, ys, ws, first):
            half = ws.shape[1] // 2
            return step(params, opt, xs, ys, ws.at[:, half:].set(0.0), first)
        return broken

    _break_span(monkeypatch, wrap)
    res = run.run_cell("train-410m-2k", SEED, 1.0, False, rehearse=True)
    assert not res["correct"]
    failing = [k for k, v in res["checked"].items() if v["value"] > v["limit"]]
    assert "gradient_norm_gap" in failing


def test_train_control_in_fp8_fails_the_limits():
    cell, sizes = rehearsal.shrink(harness.load_cell("train-410m-2k"))
    ref = train_runner.reference_readings(cell, sizes, SEED)
    ctl = train_runner.reference_readings(cell, sizes, SEED, precision="fp8")
    checked = compare.checked_from(compare.train_numbers(ctl, ref),
                                   {k: v for k, v in
                                    cell["check"]["limits"].items()
                                    if k != "compiles_in_window"})
    assert not harness.judge(checked), checked


def test_serve_cell_is_correct_untouched():
    res = run.run_cell("serve-1b-closed32", SEED, 1.5, False, rehearse=True)
    assert res["correct"], res["checked"]
    assert res["attempted"] > 32 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                   "ttft_p50_ms", "itl_p95_ms"}


def test_serve_token_altered_where_produced_is_not_correct(monkeypatch):
    from ddl_tpu.serve import InferenceEngine

    real = InferenceEngine.decode

    def broken(self, last_tokens, lengths, request_ids, active, **kw):
        nxt, logits = real(self, last_tokens, lengths, request_ids, active,
                           **kw)
        return (np.asarray(nxt) + 1) % self.config.spec.vocab, logits

    monkeypatch.setattr(InferenceEngine, "decode", broken)
    res = run.run_cell("serve-1b-closed32", SEED, 1.5, False, rehearse=True)
    assert not res["correct"]
    assert any(v["value"] > v["limit"] for k, v in res["checked"].items()
               if k.startswith("logit_gap"))


def test_serve_control_in_fp8_fails_the_limit():
    cell, sizes = rehearsal.shrink(harness.load_cell("serve-1b-closed32"))
    rng = np.random.default_rng(5)
    served = []
    for n in (40, 100, 128):
        prompt = rng.integers(0, sizes.vocab, n, dtype=np.int32)
        served.append((prompt, rng.integers(0, sizes.vocab, 16,
                                            dtype=np.int32)))
    got = serve_runner.reference_gaps(cell, sizes, SEED, served, control=True)
    checked = compare.checked_from(
        got["control"], {k: v for k, v in cell["check"]["limits"].items()
                         if k.startswith("logit_gap")})
    assert not harness.judge(checked), checked


def test_ring_zero1_on_four_devices_follows_the_reference():
    """The four-chip cell that PERF.md keeps under Open questions, at toy
    sizes on four virtual devices: the runner reads the first gradient out
    of ZeRO-1's flat, sharded first moment, and the sharded program agrees
    with the one-device reference."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    cell = {"name": "ring4", "runner": "train", "span_steps": 1,
            "trainer": {"num_workers": 2, "data_parallel": 2,
                        "scheme": "ring", "zero1": True,
                        "seq_layout": "contiguous",
                        "compute_dtype": "bfloat16", "remat": True},
            "traffic_params": {"kind": "token_rows", "seq_len": 128,
                               "batch": 4, "staged_batches": 4},
            "check": {"steps": 3, "rows": 1}}
    session = train_runner.build(cell, rehearsal.TINY, SEED)
    program = train_runner.followed_steps(session)
    train_runner.free(session)
    ref = train_runner.reference_readings(cell, rehearsal.TINY, SEED)
    numbers = compare.train_numbers(program, ref)
    assert numbers["gradient_norm_gap"] < 0.02, numbers
    assert numbers["change_norm_gap"] < 0.02, numbers
    assert max(numbers[f"loss_step{i}_rel"] for i in (1, 2, 3)) < 5e-3
