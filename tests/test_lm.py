"""Transformer LM + sequence-parallel trainer (models/transformer.py,
strategies/seq.py, data/lm.py).

The oracle chain: ``apply_lm`` with ``full_attention`` on one device is the
reference numerics; the ring/ulysses sharded trainers must reproduce its
losses and gradients on the 8-device virtual mesh, and the copy task —
solvable only by attending ``seq_len//2 - 2`` positions back, across shard
boundaries — certifies cross-shard attention end to end.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.data.lm import synthesize_copy
from ddl_tpu.models import transformer
from ddl_tpu.models.transformer import LMSpec, TINY_SPEC
from ddl_tpu.parallel import ring
from ddl_tpu.strategies.seq import LMResult, SeqConfig, SeqTrainer

SPEC = TINY_SPEC
T = 32  # divisible by the 8-device mesh
B = 4


def _batch(seed=0, n=B, seq_len=T, vocab=SPEC.vocab):
    ds = synthesize_copy(
        num_train=n, num_test=n, seq_len=seq_len, vocab=vocab, seed=seed
    )
    return (
        jnp.asarray(ds.tokens),
        jnp.asarray(ds.targets),
        jnp.asarray(ds.weights),
    )


def _oracle_attn():
    return functools.partial(ring.full_attention, causal=True)


def test_param_count_matches_spec():
    params = transformer.init_lm_params(jax.random.PRNGKey(0), SPEC)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == SPEC.num_params()


def test_copy_dataset_shapes_and_mask():
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=16, vocab=16, seed=1)
    assert ds.tokens.shape == (8, 16) and ds.test_tokens.shape == (4, 16)
    # Next-token alignment and the scored window [half-1, T-2).
    np.testing.assert_array_equal(ds.targets[:, :-1], ds.tokens[:, 1:])
    assert ds.weights[:, :7].sum() == 0 and ds.weights[:, 14:].sum() == 0
    np.testing.assert_array_equal(ds.weights[:, 7:14], 1.0)
    # Every scored target is a copy of the token half-2 = 6 positions back.
    t = np.arange(7, 14)
    np.testing.assert_array_equal(ds.targets[:, t], ds.tokens[:, t - 6])
    assert ds.tokens[:, 0].max() == 0  # BOS


def test_rope_offset_consistency():
    """RoPE on a shard with absolute positions == the shard's slice of
    RoPE on the full sequence — the property sequence sharding relies on."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 2, 8))
    full = transformer.rope(x, jnp.arange(16), 10000.0)
    shard = transformer.rope(x[:, 8:], 8 + jnp.arange(8), 10000.0)
    np.testing.assert_allclose(
        np.asarray(full[:, 8:]), np.asarray(shard), atol=1e-6
    )


def test_lm_loss_matches_manual_ce():
    tokens, targets, weights = _batch()
    params = transformer.init_lm_params(jax.random.PRNGKey(1), SPEC)
    num, den = transformer.lm_loss_sums(
        params, tokens, targets, weights, SPEC, attn_fn=_oracle_attn()
    )
    logits = transformer.apply_lm(
        params, tokens, SPEC, attn_fn=_oracle_attn()
    )
    lp = jax.nn.log_softmax(logits)
    ce = -np.take_along_axis(
        np.asarray(lp), np.asarray(targets)[..., None], axis=-1
    )[..., 0]
    expect = (ce * np.asarray(weights)).sum()
    np.testing.assert_allclose(float(num), expect, rtol=1e-5)
    assert float(den) == float(np.asarray(weights).sum())


@pytest.mark.parametrize(
    "scheme,workers", [("ring", 8), ("ulysses", 2)]
)
def test_sharded_loss_and_grads_match_oracle(scheme, workers):
    """The trainer's sharded loss program (psum-normalized, shard-offset
    RoPE, cross-shard attention) == single-device full-attention oracle,
    for both the value and the replicated-param gradients. (Ulysses shards
    heads, so its width is capped by TINY_SPEC's 2 heads.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddl_tpu.parallel.mesh import make_mesh_2d
    from ddl_tpu.strategies.seq import _shard_sums

    tokens, targets, weights = _batch(seed=3)
    params = transformer.init_lm_params(jax.random.PRNGKey(4), SPEC)

    def oracle_loss(p):
        num, den = transformer.lm_loss_sums(
            p, tokens, targets, weights, SPEC, attn_fn=_oracle_attn()
        )
        return num / den

    cfg = SeqConfig(num_workers=workers, scheme=scheme, spec=SPEC)
    mesh = make_mesh_2d(1, workers)  # the trainer's [dp, sp] mesh shape
    sums = _shard_sums(cfg, transformer.lm_loss_sums)

    # The trainer's OWN gradient pattern (_step_body / _local_loss_fn):
    # local grads of [this shard's CE sum / psum'd weight total], ONE
    # explicit psum over the mesh axes. No gradient rides a bare
    # psum transpose, so the pattern is exact on every JAX generation
    # — the value check still goes through _shard_sums'
    # psum-normalized program.
    from ddl_tpu.strategies.seq import AXES, _attn_for, _local_loss_fn
    from jax import lax

    def body(p, tk, tg, w):
        local_loss = _local_loss_fn(cfg, _attn_for(cfg), tk, tg, w)
        l_local, grads = jax.value_and_grad(local_loss)(p)
        num, den = sums(p, tk, tg, w)
        return (num / den, lax.psum(l_local, AXES),
                jax.tree.map(lambda g: lax.psum(g, AXES), grads))

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=(P(), P(), P()),
        check_vma=False,  # local-grads mode: the explicit psum owns it
    )
    seq = NamedSharding(mesh, P(None, "sp"))
    rep = NamedSharding(mesh, P())
    loss_sums, loss, grads = fn(
        jax.device_put(params, rep),
        jax.device_put(tokens, seq),
        jax.device_put(targets, seq),
        jax.device_put(weights, seq),
    )
    l0, g0 = jax.value_and_grad(oracle_loss)(params)
    np.testing.assert_allclose(float(loss_sums), float(l0), rtol=1e-4)
    np.testing.assert_allclose(float(loss), float(l0), rtol=1e-4)
    flat, flat0 = jax.tree.leaves(grads), jax.tree.leaves(g0)
    for a, b in zip(flat, flat0):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3
        )


def test_seq_trainer_rejects_bad_configs():
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=20, vocab=16, seed=0)
    with pytest.raises(ValueError, match="not divisible"):
        SeqTrainer(SeqConfig(num_workers=8, spec=SPEC), ds)  # 20 % 8 != 0
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=32, vocab=16, seed=0)
    with pytest.raises(ValueError, match="ulysses"):
        SeqTrainer(
            SeqConfig(num_workers=8, scheme="ulysses", spec=SPEC), ds
        )  # 2 heads on 8 devices
    with pytest.raises(ValueError, match="full"):
        SeqTrainer(SeqConfig(num_workers=8, scheme="full", spec=SPEC), ds)
    big = synthesize_copy(num_train=8, num_test=4, seq_len=32, vocab=64, seed=0)
    with pytest.raises(ValueError, match="vocab"):
        SeqTrainer(SeqConfig(num_workers=1, scheme="full", spec=SPEC), big)


def test_seq_trainer_learns_copy_task_ring():
    """End to end on the 8-device mesh: the copy task is unlearnable
    without cross-shard attention (scored targets live half a sequence
    away), so accuracy >> chance certifies the whole sequence-parallel
    training path — sharded loss, ring grads, Adam, eval program."""
    ds = synthesize_copy(
        num_train=256, num_test=64, seq_len=T, vocab=SPEC.vocab, seed=5
    )
    cfg = SeqConfig(
        # 10 epochs, not 6: the copy task's phase transition lands
        # between 6 and 10 depending on the init draw, and the random
        # STREAM behind a given seed differs across JAX generations
        # (jax_threefry_partitionable flipped defaults) — 10 clears the
        # transition on both (measured: 0.13 at 6 vs 0.998 at 10 on the
        # 0.4 line, same exact numerics as W=1).
        epochs=10, batch_size=32, learning_rate=3e-3, eval_every=0,
        num_workers=8, scheme="ring", spec=SPEC, seed=1,
    )
    result = SeqTrainer(cfg, ds).train(log=lambda s: None)
    assert isinstance(result, LMResult)
    chance = 1.0 / (SPEC.vocab - 1)
    assert result.final_accuracy > 10 * chance, (
        result.final_accuracy, result.history
    )
    assert np.isfinite(result.final_loss)
    assert result.tokens_per_sec > 0
    # Deterministic: same config + data => same result.
    again = SeqTrainer(cfg, ds).train(log=lambda s: None)
    assert again.final_accuracy == result.final_accuracy


def test_seq_trainer_schemes_agree():
    """ring (W=8), ulysses (W=2, head-divisible), and full (W=1) are the
    same math: short identical trainings land within fp tolerance of each
    other in final loss."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=6
    )
    results = {}
    for scheme, w in (("full", 1), ("ring", 8), ("ulysses", 2)):
        cfg = SeqConfig(
            epochs=1, batch_size=16, learning_rate=1e-3, eval_every=0,
            num_workers=w, scheme=scheme, spec=SPEC, seed=2,
        )
        results[scheme] = SeqTrainer(cfg, ds).train(log=lambda s: None)
    losses = {k: r.final_loss for k, r in results.items()}
    assert np.isclose(losses["ring"], losses["full"], rtol=1e-3), losses
    assert np.isclose(losses["ulysses"], losses["full"], rtol=1e-3), losses
    accs = {k: r.final_accuracy for k, r in results.items()}
    assert max(accs.values()) - min(accs.values()) < 0.02, accs


def test_seq_trainer_bf16_and_target_accuracy():
    """The MXU-dtype path trains, and --target-accuracy stops early at an
    eval boundary (trivial target: any accuracy >= 0)."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=7
    )
    cfg = SeqConfig(
        epochs=2, batch_size=16, eval_every=2, num_workers=8, scheme="ring",
        spec=SPEC, compute_dtype="bfloat16", target_accuracy=0.0,
    )
    result = SeqTrainer(cfg, ds).train(log=lambda s: None)
    assert np.isfinite(result.final_loss)
    # Early stop: hit at the FIRST eval point (batch index 1 of 4).
    assert result.history[-1][1] <= 2


def test_seq_trainer_checkpoint_resume(tmp_path):
    """Kill-and-resume ≡ uninterrupted: bit-for-bit when the resumed run
    keeps the saving run's cadence (the LM step has no RNG, and identical
    span lengths compile identical programs), and ~fp-identical across a
    DIFFERENT eval cadence (the elastic resume_plan realignment — span
    regrouping reassociates XLA fusion at the 1e-7 level, the same
    envelope the CNN span-parity tests pin)."""
    ds = synthesize_copy(
        num_train=64, num_test=16, seq_len=T, vocab=SPEC.vocab, seed=8
    )
    base = dict(batch_size=16, learning_rate=1e-3, num_workers=8,
                scheme="ring", spec=SPEC, seed=3)
    golden = SeqTrainer(
        SeqConfig(epochs=2, eval_every=0, **base), ds
    ).train(log=lambda s: None)

    # Stop after epoch 0 (epoch-end checkpoint), resume with the SAME
    # cadence: bit-equal.
    ckdir = str(tmp_path / "ck_same")
    SeqTrainer(SeqConfig(epochs=1, eval_every=0, **base), ds).train(
        log=lambda s: None, checkpoint_dir=ckdir
    )
    resumed = SeqTrainer(SeqConfig(epochs=2, eval_every=0, **base), ds).train(
        log=lambda s: None, checkpoint_dir=ckdir, resume=True
    )
    assert resumed.resumed_from_step == 4  # 4 batches = epoch 0
    for a, b in zip(jax.tree.leaves(golden.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert resumed.final_accuracy == golden.final_accuracy

    # Resume under a DIFFERENT cadence (eval every batch): every batch
    # still trains; params agree to span-reassociation tolerance.
    ckdir = str(tmp_path / "ck_cross")
    SeqTrainer(SeqConfig(epochs=1, eval_every=0, **base), ds).train(
        log=lambda s: None, checkpoint_dir=ckdir
    )
    crossed = SeqTrainer(SeqConfig(epochs=2, eval_every=1, **base), ds).train(
        log=lambda s: None, checkpoint_dir=ckdir, resume=True
    )
    assert crossed.resumed_from_step == 4
    assert len(crossed.history) == 4  # one eval per remaining batch
    for a, b in zip(jax.tree.leaves(golden.params),
                    jax.tree.leaves(crossed.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_seq_trainer_preemption_saves_and_stops(tmp_path):
    """should_stop flips true after the first span -> trainer saves the
    rolling checkpoint and returns preempted=True without finishing."""
    ds = synthesize_copy(
        num_train=64, num_test=16, seq_len=T, vocab=SPEC.vocab, seed=9
    )
    ckdir = str(tmp_path / "ck")
    calls = {"n": 0}

    def stop():
        calls["n"] += 1
        return calls["n"] > 1

    result = SeqTrainer(
        SeqConfig(epochs=4, batch_size=16, eval_every=2, num_workers=8,
                  scheme="ring", spec=SPEC),
        ds,
    ).train(log=lambda s: None, checkpoint_dir=ckdir, should_stop=stop)
    assert result.preempted
    import os

    assert os.path.exists(os.path.join(ckdir, "ckpt.npz"))


def test_seq_trainer_zero1_matches_replicated():
    """zero1 (reduce-scatter + chunk Adam + all_gather) is the same math
    as the replicated update: identical short trainings agree in final
    params to flatten-reassociation tolerance, and the optimizer state
    actually lives sharded (each device holds total/W + padding m/v
    elements — the ZeRO-1 memory claim)."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=10
    )
    base = dict(epochs=1, batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=8, scheme="ring", spec=SPEC, seed=4)
    rep = SeqTrainer(SeqConfig(**base), ds)
    z1 = SeqTrainer(SeqConfig(zero1=True, **base), ds)
    # Shard-resident m/v: one device's addressable shard is the chunk.
    total = z1._plan.total
    per_dev = z1.opt_state.m.addressable_shards[0].data.size
    assert per_dev == -(-total // 8), (per_dev, total)
    r_rep = rep.train(log=lambda s: None)
    r_z1 = z1.train(log=lambda s: None)
    assert np.isclose(r_z1.final_loss, r_rep.final_loss, rtol=1e-4), (
        r_z1.final_loss, r_rep.final_loss
    )
    for a, b in zip(jax.tree.leaves(r_rep.params),
                    jax.tree.leaves(r_z1.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_seq_trainer_zero1_checkpoint_cross_strategy(tmp_path):
    """Elastic across the update strategy: a replicated run's epoch-end
    checkpoint resumes under zero1 (params-shaped m/v in the checkpoint),
    and the final params match continuing the replicated run."""
    ds = synthesize_copy(
        num_train=64, num_test=16, seq_len=T, vocab=SPEC.vocab, seed=11
    )
    base = dict(batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=8, scheme="ring", spec=SPEC, seed=5)
    golden = SeqTrainer(SeqConfig(epochs=2, **base), ds).train(
        log=lambda s: None
    )
    ckdir = str(tmp_path / "ck")
    SeqTrainer(SeqConfig(epochs=1, **base), ds).train(
        log=lambda s: None, checkpoint_dir=ckdir
    )
    crossed = SeqTrainer(SeqConfig(epochs=2, zero1=True, **base), ds).train(
        log=lambda s: None, checkpoint_dir=ckdir, resume=True
    )
    assert crossed.resumed_from_step == 4
    for a, b in zip(jax.tree.leaves(golden.params),
                    jax.tree.leaves(crossed.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_seq_trainer_2d_mesh_matches_1d():
    """data_parallel x sequence-parallel (2x4 over 8 devices) is the same
    math as pure sequence parallel (1x8): identical trainings agree in
    final loss/accuracy (batch halves shard over dp rows; grads pick up
    the dp psum through shard_map's transpose)."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=12
    )
    base = dict(epochs=2, batch_size=16, learning_rate=1e-3, eval_every=0,
                scheme="ring", spec=SPEC, seed=6)
    r1 = SeqTrainer(
        SeqConfig(num_workers=8, data_parallel=1, **base), ds
    ).train(log=lambda s: None)
    r2 = SeqTrainer(
        SeqConfig(num_workers=4, data_parallel=2, **base), ds
    ).train(log=lambda s: None)
    assert np.isclose(r2.final_loss, r1.final_loss, rtol=1e-3), (
        r1.final_loss, r2.final_loss
    )
    for a, b in zip(jax.tree.leaves(r1.params), jax.tree.leaves(r2.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-3
        )


def test_seq_trainer_2d_zero1_matches_replicated():
    """The full composition — dp x sp x ZeRO-1: the combined-axes
    psum_scatter/all_gather update on the 2x4 mesh equals the replicated
    2x4 update, and m/v shards live at total/(dp*sp) per device."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=13
    )
    base = dict(epochs=1, batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=4, data_parallel=2, scheme="ring", spec=SPEC,
                seed=7)
    rep = SeqTrainer(SeqConfig(**base), ds)
    z1 = SeqTrainer(SeqConfig(zero1=True, **base), ds)
    total = z1._plan.total
    assert z1.opt_state.m.addressable_shards[0].data.size == -(-total // 8)
    r_rep = rep.train(log=lambda s: None)
    r_z1 = z1.train(log=lambda s: None)
    assert np.isclose(r_z1.final_loss, r_rep.final_loss, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(r_rep.params),
                    jax.tree.leaves(r_z1.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_seq_trainer_2d_rejects_indivisible_batch():
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=32, vocab=16,
                         seed=0)
    with pytest.raises(ValueError, match="data_parallel"):
        SeqTrainer(
            SeqConfig(batch_size=5, num_workers=4, data_parallel=2,
                      spec=SPEC), ds
        )


def test_seq_trainer_zigzag_matches_contiguous():
    """seq_layout='zigzag' is the same computation re-placed: identical
    trainings (ring, W=8) agree with the contiguous layout in final
    loss/params to attention-reassociation tolerance, and the copy task
    still trains (the permuted loss mask follows its tokens). Also
    composes with zero1."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=16
    )
    base = dict(epochs=2, batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=8, scheme="ring", spec=SPEC, seed=9)
    cont = SeqTrainer(SeqConfig(**base), ds).train(log=lambda s: None)
    zz = SeqTrainer(
        SeqConfig(seq_layout="zigzag", **base), ds
    ).train(log=lambda s: None)
    assert np.isclose(zz.final_loss, cont.final_loss, rtol=1e-3), (
        zz.final_loss, cont.final_loss
    )
    assert abs(zz.final_accuracy - cont.final_accuracy) < 0.02
    for a, b in zip(jax.tree.leaves(cont.params), jax.tree.leaves(zz.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-3
        )
    zz1 = SeqTrainer(
        SeqConfig(seq_layout="zigzag", zero1=True, **base), ds
    ).train(log=lambda s: None)
    assert np.isclose(zz1.final_loss, cont.final_loss, rtol=1e-3)


def test_seq_trainer_zigzag_rejects_bad_configs():
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=32, vocab=16,
                         seed=0)
    with pytest.raises(ValueError, match="ring"):
        SeqTrainer(
            SeqConfig(num_workers=2, scheme="ulysses", seq_layout="zigzag",
                      spec=SPEC), ds
        )
    ds24 = synthesize_copy(num_train=8, num_test=4, seq_len=24, vocab=16,
                           seed=0)
    with pytest.raises(ValueError, match="zigzag"):
        SeqTrainer(
            SeqConfig(num_workers=8, scheme="ring", seq_layout="zigzag",
                      spec=SPEC), ds24
        )  # 24 % 8 == 0 but 24 % 16 != 0 — only zigzag rejects
    big_test = synthesize_copy(num_train=8, num_test=4, seq_len=32, vocab=16,
                               seed=0)
    # Test-split vocab overflow is caught too (JAX clamps gathers
    # silently — round-4 advisor): corrupt ONLY the test tokens.
    big_test.test_tokens[0, 0] = SPEC.vocab
    with pytest.raises(ValueError, match="test vocab"):
        SeqTrainer(SeqConfig(num_workers=8, spec=SPEC), big_test)
    with pytest.raises(ValueError, match="exceeds"):
        SeqTrainer(SeqConfig(num_workers=8, batch_size=64, spec=SPEC), ds)


def test_seq_trainer_tensor_parallel_matches_1d():
    """Megatron tp is the same math re-placed: tp=2 trainings (pure tp;
    tp x ring sp; the full dp x sp x tp cube; tp + remat) match the
    single-device oracle's losses/params, and the block weights actually
    live sharded (each device holds H/tp heads' worth of wq)."""
    ds = synthesize_copy(
        num_train=32, num_test=16, seq_len=T, vocab=SPEC.vocab, seed=20
    )
    base = dict(epochs=2, batch_size=16, learning_rate=1e-3, eval_every=0,
                spec=SPEC, seed=11)
    oracle = SeqTrainer(
        SeqConfig(num_workers=1, scheme="full", **base), ds
    ).train(log=lambda s: None)
    configs = {
        "full_tp2": SeqConfig(num_workers=1, scheme="full",
                              tensor_parallel=2, **base),
        "ring2_tp2": SeqConfig(num_workers=2, scheme="ring",
                               tensor_parallel=2, **base),
        "dp2_ring2_tp2": SeqConfig(num_workers=2, data_parallel=2,
                                   tensor_parallel=2, scheme="ring",
                                   **base),
        "ring2_tp2_remat": SeqConfig(num_workers=2, scheme="ring",
                                     tensor_parallel=2, remat=True,
                                     **base),
    }
    for tag, cfg in configs.items():
        tr = SeqTrainer(cfg, ds)
        wq = tr.params["blocks"][0]["wq"]
        e = SPEC.d_model
        assert wq.addressable_shards[0].data.shape == (e, e // 2), tag
        r = tr.train(log=lambda s: None)
        assert np.isclose(r.final_loss, oracle.final_loss, rtol=1e-3), (
            tag, r.final_loss, oracle.final_loss
        )
        for a, b in zip(jax.tree.leaves(oracle.params),
                        jax.tree.leaves(r.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-3,
                err_msg=tag,
            )


def test_seq_trainer_tp_checkpoint_elastic(tmp_path):
    """Checkpoints are tp-topology-free in BOTH directions: a tp=1 save
    resumes under tp=2 (weights re-shard on load), a tp=2 save — whose
    m/v and block weights live tp-sharded — gathers to the params-shaped
    host form and resumes under tp=1; both match the uninterrupted tp=1
    golden run."""
    ds = synthesize_copy(
        num_train=32, num_test=16, seq_len=T, vocab=SPEC.vocab, seed=21
    )
    base = dict(batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=2, scheme="ring", spec=SPEC, seed=12)
    golden = SeqTrainer(SeqConfig(epochs=2, **base), ds).train(
        log=lambda s: None
    )
    for save_tp, resume_tp in ((1, 2), (2, 1)):
        ckdir = str(tmp_path / f"ck_{save_tp}to{resume_tp}")
        SeqTrainer(
            SeqConfig(epochs=1, tensor_parallel=save_tp, **base), ds
        ).train(log=lambda s: None, checkpoint_dir=ckdir)
        crossed = SeqTrainer(
            SeqConfig(epochs=2, tensor_parallel=resume_tp, **base), ds
        ).train(log=lambda s: None, checkpoint_dir=ckdir, resume=True)
        assert crossed.resumed_from_step == 2, (save_tp, resume_tp)
        for a, b in zip(jax.tree.leaves(golden.params),
                        jax.tree.leaves(crossed.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
                err_msg=f"tp {save_tp}->{resume_tp}",
            )


def test_seq_trainer_tp_rejects_bad_configs():
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=32, vocab=16,
                         seed=0)
    with pytest.raises(ValueError, match="num_heads"):
        SeqTrainer(
            SeqConfig(num_workers=1, scheme="full", tensor_parallel=3,
                      spec=SPEC), ds
        )  # 2 heads % 3
    with pytest.raises(ValueError, match="d_ff"):
        spec5 = LMSpec(vocab=32, d_model=32, num_heads=2, num_layers=1,
                       d_ff=65)
        SeqTrainer(
            SeqConfig(num_workers=1, scheme="full", tensor_parallel=2,
                      spec=spec5), ds
        )
    # zero1 x tensor_parallel is a SUPPORTED composition (the hybrid
    # sharded optimizer) — constructing it must NOT raise.
    SeqTrainer(
        SeqConfig(num_workers=2, scheme="ring", tensor_parallel=2,
                  zero1=True, spec=SPEC), ds
    )


def test_seq_trainer_zero1_tp_matches_replicated_tp_on_cube():
    """The tentpole composition: zero1 x tensor_parallel on the 2x2x2
    dp x sp x tp cube. The hybrid sharded optimizer (tp-sharded weights
    keep tp-local Adam; the replicated subtree's Adam lives as flat
    chunks over the combined dp x sp axes) is the same math as the
    replicated-Adam tp run — identical trainings agree in final
    loss/params — and the state actually lives sharded: the replicated
    subtree's m/v hold rep_total/(dp*sp) elements per device (the
    ~(dp*sp)x optimizer-memory claim) and each tp leaf's m/v mirrors its
    weight shard."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=23
    )
    base = dict(epochs=2, batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=2, data_parallel=2, tensor_parallel=2,
                scheme="ring", spec=SPEC, seed=13)
    rep = SeqTrainer(SeqConfig(**base), ds)
    hyb = SeqTrainer(SeqConfig(zero1=True, **base), ds)
    chunk = -(-hyb._hplan.rep_total // 4)  # dp*sp = 4 owners
    assert hyb.opt_state.m_flat.addressable_shards[0].data.size == chunk
    _, weight_tp = hyb._hplan.split(hyb.params)
    for m_leaf, w_leaf in zip(hyb.opt_state.m_tp, weight_tp):
        assert (m_leaf.addressable_shards[0].data.shape
                == w_leaf.addressable_shards[0].data.shape)
    r_rep = rep.train(log=lambda s: None)
    r_hyb = hyb.train(log=lambda s: None)
    assert np.isclose(r_hyb.final_loss, r_rep.final_loss, rtol=1e-5), (
        r_hyb.final_loss, r_rep.final_loss
    )
    for a, b in zip(jax.tree.leaves(r_rep.params),
                    jax.tree.leaves(r_hyb.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_seq_trainer_zero1_tp_checkpoint_elastic(tmp_path):
    """zero1 x tp checkpoints are topology- AND mode-free in both
    directions: a plain sequence-parallel save resumes under the hybrid
    zero1 x tp=2 cube (params-shaped m/v re-shard onto flat dp x sp
    chunks + tp shards on load), and a hybrid save gathers back to the
    params-shaped host form and resumes under plain tp=1; both match
    the uninterrupted plain golden run."""
    ds = synthesize_copy(
        num_train=32, num_test=16, seq_len=T, vocab=SPEC.vocab, seed=24
    )
    base = dict(batch_size=16, learning_rate=1e-3, eval_every=0,
                scheme="ring", spec=SPEC, seed=14)
    plain = dict(num_workers=2)
    hybrid = dict(num_workers=2, data_parallel=2, tensor_parallel=2,
                  zero1=True)
    golden = SeqTrainer(SeqConfig(epochs=2, **plain, **base), ds).train(
        log=lambda s: None
    )
    for tag, save_kw, resume_kw in (
        ("plain->hybrid", plain, hybrid), ("hybrid->plain", hybrid, plain)
    ):
        ckdir = str(tmp_path / f"ck_{tag.replace('->', '_')}")
        SeqTrainer(SeqConfig(epochs=1, **save_kw, **base), ds).train(
            log=lambda s: None, checkpoint_dir=ckdir
        )
        crossed = SeqTrainer(SeqConfig(epochs=2, **resume_kw, **base),
                             ds).train(
            log=lambda s: None, checkpoint_dir=ckdir, resume=True
        )
        assert crossed.resumed_from_step == 2, tag
        for a, b in zip(jax.tree.leaves(golden.params),
                        jax.tree.leaves(crossed.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
                err_msg=tag,
            )


def test_zero1_tp_step_uses_true_reduce_scatter():
    """The hybrid step's replicated-subtree gradients move via a TRUE
    fused reduce-scatter over the combined (dp, sp) axes — each device
    receives only its ~rep_total/(dp*sp)-element chunk — never a
    full-subtree (or full-flat) all-reduce. Pins the tentpole's
    collective schedule through the same optimized-HLO audit
    benchmarks/collective_bytes.py publishes (the LM analogue of
    test_sync_strategies.test_sharded_step_uses_true_reduce_scatter)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    from benchmarks.collective_bytes import audit_lm

    row = audit_lm("zero1", 2, 2, tp=2)
    rep_total = row["rep_total"]
    chunk = -(-rep_total // 4)  # dp*sp = 4 chunk owners
    rs = [o for o in row["collectives"] if o["op"] == "reduce-scatter"]
    assert any(o["max_elems"] == chunk for o in rs), (chunk, rs)
    for o in row["collectives"]:
        if o["op"] == "all-reduce":
            # Legit all-reduces remain: scalar loss sums, the tp
            # activation completions, and per-tp-shard weight-grad
            # reductions — all strictly smaller than the replicated
            # subtree a regression to psum-everything would move.
            assert o["max_elems"] < rep_total, o


def test_seq_trainer_remat_same_numbers_less_memory():
    """remat=True is the SAME training computation (jax.checkpoint
    recomputes, never reassociates differently at these sizes — losses
    and params agree to recompute tolerance) with a strictly smaller
    saved-residual footprint at long sequence: the per-block saved state
    drops from the ring sweep's residuals to the block input."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=17
    )
    base = dict(epochs=1, batch_size=16, learning_rate=1e-3, eval_every=0,
                num_workers=8, scheme="ring", spec=SPEC, seed=10)
    plain = SeqTrainer(SeqConfig(**base), ds).train(log=lambda s: None)
    rem = SeqTrainer(SeqConfig(remat=True, **base), ds).train(
        log=lambda s: None
    )
    assert np.isclose(rem.final_loss, plain.final_loss, rtol=1e-4), (
        rem.final_loss, plain.final_loss
    )
    for a, b in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(rem.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )

    # Memory: pin the autodiff-level contract — bytes of residuals the
    # backward pass SAVES across the fwd/bwd boundary. (XLA:CPU's
    # compiled temp_size does not expose buffer liveness — measured
    # unchanged under remat even as saved residuals drop 122x — so the
    # framework-level quantity is the trustworthy, backend-independent
    # one; jax._src.ad_checkpoint.saved_residuals is the programmatic
    # twin of the public print_saved_residuals. Private symbol: skip
    # the memory half, not the suite, if a JAX upgrade moves it.)
    adc = pytest.importorskip("jax._src.ad_checkpoint")

    T_ = 2048
    params = transformer.init_lm_params(jax.random.PRNGKey(19), SPEC)
    toks = jnp.zeros((2, T_), jnp.int32)
    tgts = jnp.zeros((2, T_), jnp.int32)
    wts = jnp.ones((2, T_), jnp.float32)
    attn = functools.partial(ring.full_attention, causal=True)

    def res_bytes(remat):
        def loss(p):
            n, d = transformer.lm_loss_sums(
                p, toks, tgts, wts, SPEC, attn_fn=attn, remat=remat
            )
            return n / d

        res = adc.saved_residuals(loss, params)
        return sum(
            int(np.prod(r[0].shape)) * r[0].dtype.itemsize
            for r in res if hasattr(r[0], "shape")
        )

    b_plain, b_rem = res_bytes(False), res_bytes(True)
    # Measured 465MB -> 3.8MB at these shapes; require 10x so the bound
    # survives minor autodiff changes without going stale.
    assert b_rem * 10 < b_plain, (b_plain, b_rem)


def test_seq_trainer_activation_memory_scales_with_shard():
    """The product-level memory law (the op-level twin is
    test_ring_attention_memory_is_blockwise): the COMPILED span program's
    per-device temp memory — activations, ring tiles, and the autodiff
    residuals XLA saves across the ring steps — must shrink as the same
    global sequence shards over more devices. At fixed global tokens the
    dominant saved-residual term is W tiles of (T/W)^2 = O(T^2/W), so
    widening W=2 -> W=8 must cut per-device temp by ~4x; require >3x so
    the bound survives fusion/layout drift without going stale."""
    import jax.numpy as jnp

    def temp_bytes(W):
        T_ = 1024
        ds = synthesize_copy(
            num_train=4, num_test=2, seq_len=T_, vocab=SPEC.vocab, seed=20
        )
        tr = SeqTrainer(
            SeqConfig(num_workers=W, scheme="ring", batch_size=4, spec=SPEC),
            ds,
        )
        xs = tr.stage_batches(ds.tokens, 1, 4)
        ys = tr.stage_batches(ds.targets, 1, 4)
        ws = tr.stage_batches(ds.weights, 1, 4)
        c = tr.span_program(1).lower(
            tr.params, tr.opt_state, xs, ys, ws, jnp.int32(0)
        ).compile()
        return c.memory_analysis().temp_size_in_bytes

    t2, t8 = temp_bytes(2), temp_bytes(8)
    assert t2 > 3 * t8, (t2, t8)


def test_flash_attention_matches_oracle():
    """ops/attention.py off-TPU routes the kernel's pure-JAX reference —
    fwd and grads must match the repo oracle (the TPU Pallas kernel is
    the same math; lm_bench measures it on hardware)."""
    from ddl_tpu.ops.attention import flash_attention_bthd

    key = jax.random.PRNGKey(14)
    q, k, v = (jax.random.normal(s, (2, 64, 4, 16))
               for s in jax.random.split(key, 3))
    oracle = ring.full_attention(q, k, v, causal=True)
    got = flash_attention_bthd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               atol=2e-6, rtol=1e-5)
    g1 = jax.grad(lambda q: (ring.full_attention(q, k, v, causal=True) ** 2)
                  .sum())(q)
    g2 = jax.grad(lambda q: (flash_attention_bthd(q, k, v, causal=True) ** 2)
                  .sum())(q)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                               atol=1e-5, rtol=1e-4)
    # bf16 inputs: output dtype follows q, accumulation stays fp32 (the
    # fallback upcasts like the TPU kernel), so the bf16 result rounds
    # the fp32 oracle rather than drifting.
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    got16 = flash_attention_bthd(qb, kb, vb, causal=True)
    assert got16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got16, dtype=np.float32), np.asarray(oracle),
        atol=5e-2, rtol=5e-2,
    )


# What the chip sweep of PR 27 found best at the benchmark's training shape,
# [8, 16, 2048, 64] bf16 causal on one v5e (PERF.md section 6).
SWEPT_T2048_D64 = dict(
    block_q=1024, block_k_major=1024, block_k=1024, block_b=1,
    block_q_major_dkv=1024, block_k_major_dkv=1024, block_k_dkv=1024,
    block_q_dkv=512, block_k_major_dq=512, block_k_dq=512, block_q_dq=1024)


@pytest.mark.parametrize("head_dim", [64, 128, 256])
@pytest.mark.parametrize("seq_len", [128, 256, 384, 1024, 2048, 4096, 8192])
def test_flash_block_sizes_fit_the_shape(seq_len, head_dim):
    """The pure selection: whatever (T, D), a ``BlockSizes`` the bundled
    kernels accept (each block a whole number of 128s that divides T,
    each minor block dividing its major), with the backward's blocks."""
    import dataclasses

    from ddl_tpu.ops.attention import FLASH_BLOCK, flash_block_sizes

    bs = flash_block_sizes(seq_len, head_dim)  # __post_init__ checks pairs
    assert bs.has_backward_blocks
    got = dataclasses.asdict(bs)
    assert got.pop("block_b") == 1  # the choice sees no batch to divide
    for name, block in got.items():
        assert block >= FLASH_BLOCK and block % FLASH_BLOCK == 0, name
        assert seq_len % block == 0, (name, block)
    for minor, major in (("block_k", "block_k_major"),
                         ("block_k_dkv", "block_k_major_dkv"),
                         ("block_q_dkv", "block_q_major_dkv"),
                         ("block_k_dq", "block_k_major_dq")):
        assert got[major] % got[minor] == 0, (minor, major)
    if seq_len == 128:  # one block: the kernel's own default
        assert set(got.values()) == {FLASH_BLOCK}
    if (seq_len, head_dim) == (2048, 64):
        assert dataclasses.asdict(bs) == SWEPT_T2048_D64
    # Wider heads never get wider blocks (VMEM holds [block, D] tiles).
    wide = dataclasses.asdict(flash_block_sizes(seq_len, 2 * head_dim))
    assert all(wide[name] <= block for name, block in got.items())


def test_seq_trainer_flash_matches_xla():
    """attn_impl='flash' (reference path on the CPU mesh) trains to the
    same result as the einsum kernel, for both schemes that support it;
    ring + flash is rejected."""
    ds = synthesize_copy(
        num_train=64, num_test=32, seq_len=T, vocab=SPEC.vocab, seed=15
    )
    base = dict(epochs=1, batch_size=16, learning_rate=1e-3, eval_every=0,
                spec=SPEC, seed=8)
    for scheme, w in (("full", 1), ("ulysses", 2)):
        xla = SeqTrainer(
            SeqConfig(num_workers=w, scheme=scheme, **base), ds
        ).train(log=lambda s: None)
        fl = SeqTrainer(
            SeqConfig(num_workers=w, scheme=scheme, attn_impl="flash",
                      **base), ds
        ).train(log=lambda s: None)
        assert np.isclose(fl.final_loss, xla.final_loss, rtol=1e-4), (
            scheme, fl.final_loss, xla.final_loss
        )
        for a, b in zip(jax.tree.leaves(xla.params),
                        jax.tree.leaves(fl.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-3
            )
    with pytest.raises(ValueError, match="flash"):
        SeqTrainer(
            SeqConfig(num_workers=8, scheme="ring", attn_impl="flash",
                      **base), ds
        )
