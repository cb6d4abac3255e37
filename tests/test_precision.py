"""Mixed precision end-to-end (ISSUE 19): the precision policy's two
contracts and the int8 KV pool's one.

Training: ``precision="fp32"`` (and ``None``) must compile the
BYTE-IDENTICAL pre-policy program in every step body — the off-path
discipline is pinned as lowered-HLO text equality over the strategy
matrix (plain / zero1 / tp / zero1+tp / pipeline) and the single-chip
CNN step. ``precision="bf16"`` trains: its loss trajectory tracks the
fp32 run at bf16 tolerance while master weights and Adam moments stay
fp32 leaves (the arXiv 2204.06514 split ddl_tpu.precision documents).

Serving: ``kv_dtype="int8"`` stores the paged pool as int8 rows with
fp32 per-head scales. Off-path the fp32 pool must flatten to its three
historical leaves and compile programs that mention no ``s8`` — the
same byte-identity discipline, at the pytree/HLO level. On-path: greedy
tokens match the fp32 pool on the tiny spec, quantization error is
bounded by half a scale step, and a dumped page set survives
preempt/adopt spill→restore BIT-identically (payload, scales, and
positions) with the continuation matching an unpreempted oracle — at
tp=1 in tier-1 and tp=2 under the slow marker.

Every scheduler-driving test stays inside the tier-1 audit budget
(tests/test_markers.py: <= 64 estimated tokens, <= 2 topologies — the
ISSUE 19 variant ledger included).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu import precision
from ddl_tpu.data.lm import synthesize_copy, synthesize_mixed_traffic
from ddl_tpu.models import cnn
from ddl_tpu.models.transformer import TINY_SPEC, LMSpec
from ddl_tpu.ops import kv_cache
from ddl_tpu.serve import (
    ClassSpec,
    InferenceEngine,
    Request,
    Router,
    RouterConfig,
    Scheduler,
    ServeConfig,
)
from ddl_tpu.serve.cache import kv_row_bytes
from ddl_tpu.serve.engine import pack_args
from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
from ddl_tpu.train.config import TrainConfig
from ddl_tpu.train.trainer import make_train_step
from ddl_tpu.utils import load_checkpoint, save_checkpoint

SPEC = LMSpec(vocab=17, d_model=8, num_heads=2, num_layers=2, d_ff=16)

# The conftest's narrow CNN (same widths as the CLI --tiny preset).
CNN_SPECS = cnn.make_param_specs(
    conv_channels=cnn.TINY_CONV_CHANNELS, fc_sizes=cnn.TINY_FC_SIZES
)


# -- policy resolution --------------------------------------------------------


def test_policy_resolution_matrix():
    """The ONE resolution rule (precision.resolve): None/None is fp32,
    a bare legacy compute_dtype stays the pre-policy bf16 (compute
    casts, fp32 reductions), the named policies engage fully, and the
    two knobs disagreeing is a loud error — not a silent mislabel."""
    p = precision.resolve(None, None)
    assert p.name == "fp32" and not p.is_mixed and p.mfu_kind == "fp32"
    assert p.compute_dtype is None and not p.reduces_in_bf16

    legacy = precision.resolve(None, "bfloat16")
    assert legacy.name == "bf16" and legacy.legacy and legacy.is_mixed
    assert legacy.compute_dtype == jnp.bfloat16
    assert not legacy.reduces_in_bf16  # pre-policy programs unchanged
    assert legacy.mfu_kind == "bf16"  # ...but the MXU row is honest

    full = precision.resolve("bf16", None)
    assert full.reduces_in_bf16 and full.mfu_kind == "bf16"
    assert precision.resolve("fp32", None).compute_dtype is None
    # Agreeing knobs are allowed; disagreeing knobs raise.
    assert precision.resolve("bf16", "bfloat16").reduces_in_bf16
    with pytest.raises(ValueError, match="conflicts"):
        precision.resolve("fp32", "bfloat16")
    with pytest.raises(ValueError, match="unknown precision"):
        precision.resolve("fp16", None)
    with pytest.raises(ValueError, match="KV-STORAGE"):
        precision.resolve(None, "int8")


def test_grad_cast_hooks_touch_only_float_leaves():
    """cast_grads moves float leaves to bf16 and upcast_grads back to
    fp32; integer leaves (step counters, token ids) pass through both
    untouched; and for fp32/legacy policies BOTH hooks are Python-level
    identity — the very same tree object, so the off-path step bodies
    trace the pre-policy program."""
    tree = {"w": jnp.ones((3,), jnp.float32), "step": jnp.int32(7)}
    for p in (precision.resolve(None, None),
              precision.resolve(None, "bfloat16")):
        assert p.cast_grads(tree) is tree
        assert p.upcast_grads(tree) is tree
    p = precision.resolve("bf16", None)
    down = p.cast_grads(tree)
    assert down["w"].dtype == jnp.bfloat16
    assert down["step"].dtype == jnp.int32
    up = p.upcast_grads(down)
    assert up["w"].dtype == jnp.float32 and up["step"].dtype == jnp.int32


# -- fp32 off-path: byte-identical programs -----------------------------------


def _span_hlo(cfg, ds):
    tr = SeqTrainer(cfg, ds)
    xs = tr.stage_batches(ds.tokens, 2, 4)
    ys = tr.stage_batches(ds.targets, 2, 4)
    ws = tr.stage_batches(ds.weights, 2, 4)
    return tr.span_program(2).lower(
        tr.params, tr.opt_state, xs, ys, ws, jnp.int32(0)
    ).as_text()


def test_fp32_policy_seq_programs_byte_identical():
    """precision="fp32" lowers the byte-identical program in EVERY seq
    step body — plain, zero1, tensor-parallel, the hybrid zero1+tp, and
    the pipeline schedule. HLO text equality, the strongest off-path
    pin the repo uses (stricter than numerics: no reordered op
    survives)."""
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=8,
                         vocab=SPEC.vocab, seed=0)
    base = dict(batch_size=4, scheme="full", num_workers=1, spec=SPEC,
                epochs=1)
    for extra in ({}, {"zero1": True}, {"tensor_parallel": 2},
                  {"zero1": True, "tensor_parallel": 2},
                  {"pipeline_parallel": 2, "microbatches": 2}):
        a = _span_hlo(SeqConfig(**base, **extra), ds)
        b = _span_hlo(SeqConfig(**base, **extra, precision="fp32"), ds)
        assert a == b, f"fp32 policy changed the {extra or 'plain'} program"


def test_fp32_policy_cnn_step_byte_identical():
    """The single-chip CNN trainer's step under precision="fp32" is the
    byte-identical default program (make_train_step reads the policy's
    compute_dtype: None = the no-cast path)."""
    from ddl_tpu.ops.optimizers import adam_init

    params = cnn.init_params(jax.random.PRNGKey(0), specs=CNN_SPECS)
    opt = adam_init(params)
    x = jnp.zeros((4, 28, 28, 1), jnp.float32)
    y = jnp.zeros((4, 10), jnp.float32)
    rng = jax.random.PRNGKey(1)

    def hlo(cfg):
        step = make_train_step(cfg)
        return jax.jit(step).lower(params, opt, x, y, rng).as_text()

    assert hlo(TrainConfig()) == hlo(TrainConfig(precision="fp32"))


def test_fp32_paged_serve_off_path_no_int8():
    """Off-path serve discipline at both levels: a full-precision paged
    cache flattens to its THREE historical leaves (the None scale
    fields vanish from the pytree, so donation/sharding treat the cache
    exactly as before ISSUE 19), and the lowered fp32 decode program
    text mentions no s8 — the int8 pool left zero trace."""
    cfg = dict(spec=TINY_SPEC, slots=2, capacity=32, page_size=8,
               num_pages=16)
    eng = InferenceEngine(ServeConfig(**cfg))
    assert not eng.quantized
    assert len(jax.tree.leaves(eng.cache)) == 3
    packed = pack_args((
        np.zeros(2, np.int32), np.zeros(2, np.int32),
        np.zeros(2, np.int32), np.zeros(2, bool),
        np.zeros((2, 2), np.int32),
    ))
    txt = eng._decode_paged(2).lower(
        eng.params, eng.cache, jnp.asarray(packed)).as_text()
    assert " s8[" not in txt
    # The int8 pool carries exactly the two extra scale planes.
    q = InferenceEngine(ServeConfig(**cfg, kv_dtype="int8"))
    assert q.quantized and len(jax.tree.leaves(q.cache)) == 5


# -- bf16 on-path: trains, tracks fp32, masters stay fp32 ---------------------


def test_bf16_cnn_loss_tracks_fp32_masters_stay_fp32():
    """Five bf16 CNN steps on fixed data: every loss is finite and
    within bf16 tolerance of the fp32 trajectory, and the params
    leaving each step are STILL fp32 leaves (master weights — the
    in-loss cast's transpose upcasts cotangents, so Adam runs fp32)."""
    from ddl_tpu.ops.optimizers import adam_init

    key = jax.random.PRNGKey(2)
    params0 = cnn.init_params(key, specs=CNN_SPECS)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 28, 28, 1))
    y = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(4), (8,), 0, 10), 10
    )

    def run(cfg):
        step = jax.jit(make_train_step(cfg))
        p, o = params0, adam_init(params0)
        losses = []
        for i in range(5):
            p, o, loss = step(p, o, x, y, jax.random.PRNGKey(i))
            losses.append(float(loss))
        return losses, p

    cfg = dict(learning_rate=1e-3, keep_prob=1.0)
    ref, p_ref = run(TrainConfig(**cfg))
    got, p_bf = run(TrainConfig(**cfg, precision="bf16"))
    assert all(np.isfinite(got)), got
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.05)
    for leaf in jax.tree.leaves(p_bf):
        assert leaf.dtype == jnp.float32
    # The trajectories agree loss-wise AND the masters stay close.
    for a, b in zip(jax.tree.leaves(p_bf), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2)


def test_bf16_lm_loss_tracks_fp32():
    """The distributed twin: one 2-step LM span under precision="bf16"
    (bf16 activations AND bf16 gradient reduction) lands within bf16
    tolerance of the fp32 span, with fp32 master params out."""
    ds = synthesize_copy(num_train=8, num_test=4, seq_len=8,
                         vocab=SPEC.vocab, seed=0)
    base = dict(batch_size=4, scheme="full", num_workers=1, spec=SPEC,
                epochs=1)

    def run(cfg):
        tr = SeqTrainer(cfg, ds)
        xs = tr.stage_batches(ds.tokens, 2, 4)
        ys = tr.stage_batches(ds.targets, 2, 4)
        ws = tr.stage_batches(ds.weights, 2, 4)
        out = tr.span_program(2)(tr.params, tr.opt_state, xs, ys, ws,
                                 jnp.int32(0))
        return float(out[2]), out[0]

    ref, _ = run(SeqConfig(**base, precision="fp32"))
    got, params = run(SeqConfig(**base, precision="bf16"))
    assert np.isfinite(got)
    assert abs(got - ref) < 0.1 * abs(ref) + 0.05, (got, ref)
    for leaf in jax.tree.leaves(params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32


# -- int8 KV pool -------------------------------------------------------------


def test_int8_quantize_dequantize_error_bound():
    """The op-level contract: per-head symmetric absmax — dequantized
    error is bounded by half a scale step elementwise, all-zero rows
    round-trip EXACTLY (scale 1.0, payload 0), payload is int8 in
    [-127, 127], and the scale drops the trailing head axis."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 4, 16)) * 3.0
    x = x.at[0, 0].set(0.0)  # an all-zero head row
    q, scale = kv_cache.quantize_rows(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    assert int(jnp.max(jnp.abs(q))) <= 127
    back = kv_cache.dequantize_rows(q, scale, jnp.float32)
    err = np.asarray(jnp.abs(back - x))
    bound = np.asarray(scale)[..., None] / 2 + 1e-7
    assert (err <= bound).all(), err.max()
    np.testing.assert_array_equal(np.asarray(back[0, 0]), 0.0)
    # bf16 storage dtype out: the cast happens AFTER the exact fp32
    # multiply, so the result is the bf16 rounding of the fp32 dequant.
    back16 = kv_cache.dequantize_rows(q, scale, jnp.bfloat16)
    assert back16.dtype == jnp.bfloat16


def test_kv_row_bytes_envelope():
    """The byte-envelope arithmetic the bench sizes pools with: fp32
    rows cost 2*L*H*D*4, int8 rows 2*L*H*(D+4) (1-byte payload + the
    amortized 4-byte per-head scale), compression 4D/(D+4) — 3.2x at
    head_dim 16."""
    s = TINY_SPEC
    L, H, D = s.num_layers, s.num_heads, s.d_model // s.num_heads
    assert kv_row_bytes(s, None) == 2 * L * H * D * 4
    assert kv_row_bytes(s, "int8") == 2 * L * H * (D + 4)
    ratio = kv_row_bytes(s, None) / kv_row_bytes(s, "int8")
    assert ratio == pytest.approx(4 * D / (D + 4))
    with pytest.raises(ValueError, match="kv_dtype"):
        kv_row_bytes(s, "fp8")


def test_serve_kv_dtype_validation_both_directions():
    """Loud ctor (the PR 4/6 pattern): unknown kv_dtype and int8 on the
    contiguous layout are construction errors naming the fix; the
    matching good config constructs quantized."""
    good = dict(spec=TINY_SPEC, slots=2, capacity=32)
    with pytest.raises(ValueError, match="kv_dtype"):
        InferenceEngine(ServeConfig(**good, page_size=8, kv_dtype="fp8"))
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(ServeConfig(**good, kv_dtype="int8"))
    eng = InferenceEngine(ServeConfig(**good, page_size=8,
                                      kv_dtype="int8"))
    assert eng.quantized and eng.cache.k.dtype == jnp.int8
    assert eng.cache.k_scale.dtype == jnp.float32


def test_int8_tokens_match_fp32_greedy():
    """On-path acceptance at tier-1 scale: the int8 pool's greedy
    tokens equal the fp32 pool's on the tiny spec (per-head absmax at
    these magnitudes leaves the argmax untouched — the bench measures
    the general-tolerance version at scale)."""
    cfg = dict(spec=TINY_SPEC, slots=2, capacity=32, page_size=8,
               num_pages=16)
    host = jax.device_get(InferenceEngine(ServeConfig(**cfg)).params)
    prompt = (np.arange(1, 11) * 3) % TINY_SPEC.vocab

    def run(extra):
        s = Scheduler(InferenceEngine(ServeConfig(**cfg, **extra),
                                      params=host))
        done, _ = s.run([Request(id=1, prompt=prompt, max_new_tokens=8)])
        return done[1].tokens

    assert run(dict(kv_dtype="int8")) == run(dict())


def _preempt_adopt_roundtrip(tp: int):
    """Spill→restore: preempt mid-decode, adopt elsewhere, require the
    restored pages BIT-identical (payload + scales + pos) and the
    continuation equal to an unpreempted oracle."""
    cfg = dict(spec=TINY_SPEC, slots=2, capacity=32, page_size=8,
               num_pages=16, kv_dtype="int8", tensor_parallel=tp)
    host = jax.device_get(InferenceEngine(ServeConfig(**cfg)).params)
    prompt = (np.arange(1, 11) * 3) % TINY_SPEC.vocab
    mk = lambda: Scheduler(InferenceEngine(ServeConfig(**cfg),
                                           params=host))
    req = lambda: Request(id=11, prompt=prompt, max_new_tokens=8)
    src, oracle, dst = mk(), mk(), mk()
    for s in (src, oracle, dst):
        s.begin()
    src.submit(req())
    oracle.submit(req())
    for _ in range(4):
        src.tick()
        oracle.tick()
    pre = src.preempt(11)
    # Int8 pools travel as (payload, scale) pairs end to end.
    assert isinstance(pre.k, tuple) and isinstance(pre.v, tuple)
    assert pre.k[0].dtype == np.int8 and pre.k[1].dtype == np.float32
    slot = dst.adopt(pre)
    # The restored slot's pages are the dumped bytes, bit for bit.
    (k2, ks2), (v2, vs2), pos2 = dst.engine.dump_slot_pages(slot)
    np.testing.assert_array_equal(k2, pre.k[0])
    np.testing.assert_array_equal(ks2, pre.k[1])
    np.testing.assert_array_equal(v2, pre.v[0])
    np.testing.assert_array_equal(vs2, pre.v[1])
    np.testing.assert_array_equal(pos2, pre.pos)
    while not oracle.idle:
        oracle.tick()
    while not dst.idle:
        dst.tick()
    want, _ = oracle.collect()
    got, _ = dst.collect()
    assert got[11].tokens == want[11].tokens


def test_int8_preempt_adopt_bit_identical_tp1():
    _preempt_adopt_roundtrip(1)


@pytest.mark.slow
def test_int8_preempt_adopt_bit_identical_tp2():
    """tp=2: per-shard heads dump/restore through the SAME pair
    protocol — the assembled host arrays round-trip bitwise and the
    adopted continuation matches the tp=2 oracle."""
    _preempt_adopt_roundtrip(2)


def test_int8_dump_needs_matching_pool():
    """Mismatched hand-offs fail LOUDLY in both directions: an int8
    dump refuses to land in a full-precision pool and vice versa — a
    silent dequant-to-garbage would poison the adopted request's whole
    continuation."""
    base = dict(spec=TINY_SPEC, slots=2, capacity=32, page_size=8,
                num_pages=16)
    fp = InferenceEngine(ServeConfig(**base))
    q = InferenceEngine(ServeConfig(**base, kv_dtype="int8"))
    k = np.zeros((TINY_SPEC.num_layers, 1, 8, TINY_SPEC.num_heads,
                  TINY_SPEC.d_model // TINY_SPEC.num_heads), np.float32)
    pos = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="full-precision"):
        fp.load_slot_pages(0, (k, k[..., 0]), (k, k[..., 0]), pos)
    with pytest.raises(ValueError, match="int8 pool"):
        q.load_slot_pages(0, k, k, pos)


def test_int8_disagg_handoff_transparent():
    """The third compressed hand-off surface (with preempt/adopt and
    crash requeue): a 1-prefill + 1-decode int8 fleet reproduces the
    int8 colocated fleet's tokens on the same seeded stream — the
    per-tick prefill→decode page transfer moves (payload, scale) pairs
    without a dequant round-trip — with every multi-token request
    crossing exactly once and both quantized pools byte-whole after."""
    traffic = synthesize_mixed_traffic(
        classes={"chat": dict(rate=0.6, prompt_min=6, prompt_max=10,
                              max_new_tokens=4)},
        horizon=8, vocab=TINY_SPEC.vocab, seed=1, max_requests=6,
    )
    cfg = ServeConfig(spec=TINY_SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12, kv_dtype="int8")
    rc = RouterConfig(serve=cfg, replicas=2, classes=(ClassSpec("chat"),))
    done_c, _ = Router(rc).run(traffic)

    r_dis = Router(dataclasses.replace(rc, roles=("prefill", "decode")))
    done_d, stats_d = r_dis.run(traffic)

    assert {i: done_d[i].tokens for i in done_d} == \
        {i: done_c[i].tokens for i in done_c}
    multi = sum(1 for c in done_c.values() if len(c.tokens) > 1)
    assert stats_d.disagg["handoffs"] == multi > 0
    assert stats_d.disagg["handoff_pages"] > 0
    for eng in r_dis.engines:
        assert eng.quantized
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0


# -- checkpoint dtype pins ----------------------------------------------------


def test_checkpoint_dtype_mismatch_names_leaf(tmp_path):
    """Loading a checkpoint into a template whose leaf dtype differs is
    a ValueError NAMING the leaf and both dtypes (ISSUE 19 satellite:
    precision policies keep master state fp32 — a silent cast on load
    would let a bf16-template restore masquerade as the saved run)."""
    path = tmp_path / "ckpt.npz"
    tree = {"w": np.ones((3,), np.float32), "n": np.int32(2)}
    save_checkpoint(path, tree, step=1)
    got, step, _ = load_checkpoint(path, tree)
    assert step == 1 and got["w"].dtype == np.float32
    bad = {"w": np.ones((3,), np.float16), "n": np.int32(2)}
    with pytest.raises(ValueError, match=r"w.*float32"):
        load_checkpoint(path, bad)
