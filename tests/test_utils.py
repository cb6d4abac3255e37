"""Checkpoint + metrics unit tests (gap-fill subsystems, SURVEY.md §5)."""

import jax
import numpy as np
import pytest

from ddl_tpu.models import cnn
from ddl_tpu.ops import adam_init
from ddl_tpu.utils import StepTimer, load_checkpoint, save_checkpoint


def test_checkpoint_roundtrip(tmp_path):
    params = cnn.init_params(jax.random.PRNGKey(0))
    opt = adam_init(params)
    path = tmp_path / "ckpt.npz"
    save_checkpoint(path, {"params": params, "opt": opt}, step=7,
                    extra={"accuracy": 0.99})
    like = {"params": params, "opt": adam_init(params)}
    tree, step, extra = load_checkpoint(path, like)
    assert step == 7
    assert extra["accuracy"] == 0.99
    for n in cnn.PARAM_NAMES:
        np.testing.assert_array_equal(tree["params"][n], np.asarray(params[n]))
    assert int(tree["opt"].step) == 0


def test_checkpoint_shape_mismatch(tmp_path):
    params = cnn.init_params(jax.random.PRNGKey(0))
    path = tmp_path / "ckpt.npz"
    save_checkpoint(path, {"p": params["v13"]})
    with pytest.raises(ValueError):
        load_checkpoint(path, {"p": params["v12"]})


def test_checkpoint_atomic_no_partial(tmp_path):
    # A failed save must not clobber the existing checkpoint.
    path = tmp_path / "ckpt.npz"
    save_checkpoint(path, {"a": np.arange(3.0)}, step=1)

    class Boom:
        pass

    with pytest.raises(Exception):
        save_checkpoint(path, {"a": Boom()})  # not array-convertible
    tree, step, _ = load_checkpoint(path, {"a": np.zeros(3)})
    assert step == 1
    leftovers = [p for p in path.parent.iterdir() if ".tmp" in p.name]
    assert not leftovers


def test_step_timer():
    t = StepTimer(batch_size=10, warmup=1)
    for _ in range(4):
        with t.step():
            pass
    s = t.stats()
    assert s.steps == 3
    assert s.images_per_sec > 0


def _timer_with(times_s):
    """A StepTimer whose recorded step durations are exactly
    ``times_s`` — percentile math must be pinnable on KNOWN samples,
    not on wall-clock noise."""
    t = StepTimer()
    t._times = list(times_s)
    t._images = [1] * len(times_s)
    return t


def test_step_stats_percentiles_known_samples():
    """p50/p95/p99 on [10, 20, 30, 40] ms: the contract is
    np.percentile's LINEAR-INTERPOLATION definition (not nearest-rank) —
    p50 = midpoint 25ms, p95 = 38.5ms, p99 = 39.7ms. A silent switch to
    nearest-rank would report 30/40/40 and skew every serving SLO row
    (BASELINE.md percentile columns)."""
    s = _timer_with([0.010, 0.020, 0.030, 0.040]).stats()
    assert s.steps == 4
    assert s.mean_ms == pytest.approx(25.0)
    assert s.p50_ms == pytest.approx(25.0)
    assert s.p95_ms == pytest.approx(38.5)
    assert s.p99_ms == pytest.approx(39.7)
    assert s.total_s == pytest.approx(0.100)


def test_step_stats_percentiles_n1_n2_edges():
    """The n=1 and n=2 edges, where nearest-rank and interpolation
    definitions diverge most: one sample means EVERY percentile is that
    sample; two samples interpolate between them (p50 = midpoint,
    p95/p99 near — but below — the max; nearest-rank would snap all
    three to the max)."""
    s1 = _timer_with([0.012]).stats()
    assert (s1.p50_ms, s1.p95_ms, s1.p99_ms) == (
        pytest.approx(12.0), pytest.approx(12.0), pytest.approx(12.0)
    )
    s2 = _timer_with([0.010, 0.030]).stats()
    assert s2.p50_ms == pytest.approx(20.0)
    assert s2.p95_ms == pytest.approx(29.0)  # 10 + 0.95 * 20
    assert s2.p99_ms == pytest.approx(29.8)  # 10 + 0.99 * 20
    assert s2.p50_ms < s2.p95_ms < s2.p99_ms < 30.0


def test_step_stats_empty_constructs_all_fields_explicitly():
    """The n=0 StepStats (ISSUE 5 satellite): every field pinned to
    exactly zero BY NAME — the old positional 6-tuple silently leaned
    on the p99_ms default, one field reorder away from assigning a
    percentile into total_s."""
    from ddl_tpu.utils.metrics import StepStats

    z = StepStats.from_times([])
    assert (z.steps, z.mean_ms, z.p50_ms, z.p95_ms, z.p99_ms,
            z.total_s, z.images_per_sec) == (0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0)
    assert z == StepStats(steps=0, mean_ms=0.0, p50_ms=0.0, p95_ms=0.0,
                          p99_ms=0.0, total_s=0.0, images_per_sec=0.0)


def test_step_stats_tokens_per_sec_alias_and_line_unit():
    """``tokens_per_sec`` is the honestly-named read of the throughput
    field for the token-counting paths (LM/serve), and ``line()`` can
    label the unit (ISSUE 5 satellite — token throughput was reported
    under the misnamed img/s)."""
    from ddl_tpu.utils.metrics import StepStats

    s = StepStats.from_times([0.5, 0.5], images=[100, 100])
    assert s.images_per_sec == pytest.approx(200.0)
    assert s.tokens_per_sec == s.images_per_sec
    assert s.line().endswith("200 img/s")
    assert s.line(unit="tok/s").endswith("200 tok/s")


def test_step_stats_warmup_exclusion_and_empty():
    """Warmup steps leave the percentile window (but stay in total_s,
    the throughput bracket); an all-warmup timer yields the zero
    StepStats rather than a nan percentile."""
    t = StepTimer(warmup=2)
    t._times = [1.000, 1.000, 0.010, 0.030]
    t._images = [1, 1, 1, 1]
    s = t.stats()
    assert s.steps == 2
    assert s.p50_ms == pytest.approx(20.0)
    assert t.total_s == pytest.approx(2.040)
    empty = StepTimer(warmup=2)
    empty._times = [1.0]
    empty._images = [1]
    z = empty.stats()
    assert z.steps == 0 and z.p99_ms == 0.0


def test_force_within_passes_normal_and_raises_on_hang():
    """Accelerator-death detection (force_within): a completing barrier is
    transparent, a genuinely wedged one raises with the --resume recovery
    route, and an error inside the barrier surfaces as itself (never
    masked by the timeout message)."""
    import time as _time

    import jax.numpy as jnp
    import pytest

    from ddl_tpu.train import trainer as tr

    # Normal path: completes, no error (timeout generous).
    tr.force_within(jnp.arange(4.0), 30.0, "test fetch")

    # Hang path: monkeypatch-free — a leaf whose barrier never returns.
    class Wedged:
        def block_until_ready(self):
            _time.sleep(60)

    from ddl_tpu.parallel.mesh import AcceleratorTimeout

    with pytest.raises(AcceleratorTimeout, match="--resume"):
        tr.force_within(Wedged(), 0.2, "wedged fetch")

    # <= 0 disables the watchdog entirely (negative is NOT an instant
    # timeout): the wedged fetch is simply not guarded... so use a real
    # tree to prove the call goes straight through.
    tr.force_within(jnp.arange(4.0), -1.0, "unguarded fetch")
    assert tr.guarded(lambda: 7, 0.0, "plain call") == 7

    # Error path: the real exception propagates, not the timeout wording.
    class Broken:
        def block_until_ready(self):
            raise ValueError("device exploded")

    with pytest.raises(ValueError, match="device exploded"):
        tr.force_within(Broken(), 30.0, "broken fetch")


def test_bench_conv_matmul_env_validated_up_front(monkeypatch):
    """A BENCH_CONV_MATMUL typo must die as a clean SystemExit at config
    time — before any device work — not as a KeyError deep in jit
    tracing during the first sweep row (round-5 advice #1)."""
    import pytest

    import bench

    monkeypatch.setenv("BENCH_CONV_MATMUL", "tails")
    with pytest.raises(SystemExit, match="tails"):
        bench._conv_matmul_mode()
    monkeypatch.setenv("BENCH_CONV_MATMUL", "tail")
    assert bench._conv_matmul_mode() == "tail"


def test_steps_scan_matches_lax_scan():
    """steps_scan's three regimes (k==1 inlined, k<=cap unrolled off-TPU,
    k>cap rolled) are all exactly lax.scan semantics: same carry, same
    stacked outputs — the XLA:CPU while-op pathology fix must never change
    what a span computes."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.train.trainer import SCAN_UNROLL_CAP, steps_scan

    def body(c, xy):
        a, b = xy
        c = c * 0.5 + a - b
        return c, c * 2.0

    for k in (1, 3, SCAN_UNROLL_CAP, SCAN_UNROLL_CAP + 8):
        xs = (jnp.arange(k, dtype=jnp.float32),
              jnp.linspace(0.0, 1.0, k))
        init = jnp.float32(1.0)
        want_c, want_y = jax.lax.scan(body, init, xs)
        got_c, got_y = jax.jit(
            lambda i, x: steps_scan(body, i, x, k)
        )(init, xs)
        np.testing.assert_allclose(got_c, want_c, rtol=1e-6, err_msg=f"k={k}")
        np.testing.assert_allclose(got_y, want_y, rtol=1e-6, err_msg=f"k={k}")
        assert got_y.shape == (k,)
