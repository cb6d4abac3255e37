"""Serving subsystem (ddl_tpu/serve/, ops/kv_cache.py,
transformer.apply_lm_cached, checkpoint.load_params).

The oracle chain extends training's: full-forward ``apply_lm`` is the
reference numerics, and incremental KV-cache decode must reproduce its
logits at every position — for tp=1 and tp=2 meshes — while the
continuous-batching scheduler must produce EXACTLY the tokens each
request would get decoded alone (sampling keys depend only on
(seed, request_id, token_index), never on batch composition).

Fast decode-parity smokes stay unmarked (the tier-1 gate); the long
sweeps (staggered-arrival batching grids, capacity-scale runs) are
``slow`` so tier-1 stays inside its wall budget on the 2-CPU container.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.data.lm import (
    synthesize_copy,
    synthesize_prompts,
    synthesize_shared_prefix_prompts,
)
from ddl_tpu.models import transformer
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.ops import kv_cache
from ddl_tpu.ops.kv_cache import PAD_POS
from ddl_tpu.parallel import ring
from ddl_tpu.serve import (
    InferenceEngine,
    PrefixIndex,
    Request,
    Scheduler,
    ServeConfig,
)

SPEC = TINY_SPEC


def _oracle_attn():
    return functools.partial(ring.full_attention, causal=True)


def _params(seed=0):
    return transformer.init_lm_params(jax.random.PRNGKey(seed), SPEC)


def _empty_cache(b, c, dtype=jnp.float32):
    shape = (SPEC.num_layers, b, c, SPEC.num_heads, SPEC.head_dim)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
            jnp.full((b, c), PAD_POS, jnp.int32))


# -- ops/kv_cache.py ---------------------------------------------------------


def test_kv_attend_matches_full_attention():
    """attend() against a cache whose rows hold positions 0..T-1 ==
    full_attention over the same q/k/v — same mask constant, same
    einsum, same fp32 softmax."""
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(s, (2, 12, 2, 8))
               for s in jax.random.split(key, 3))
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    got = kv_cache.attend(q, k, v, pos, pos)
    want = ring.full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)


def test_kv_attend_masks_pad_and_stale_rows():
    """PAD_POS rows are invisible whatever junk their k/v hold: attend
    over a cache with junk beyond the valid prefix == attend over the
    valid prefix alone."""
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 3, 2, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 8, 2, 8))
    qpos = jnp.asarray([[0, 1, 2]])
    kpos = jnp.where(jnp.arange(8) < 3, jnp.arange(8), PAD_POS)[None]
    got = kv_cache.attend(q, k, v, qpos, kpos)
    want = ring.full_attention(q, k[:, :3], v[:, :3], causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)


def test_kv_append_rows_wraps_as_a_ring():
    """append_rows at caller-wrapped indices overwrites the oldest rows —
    the ring-buffer contract (capacity 4, writes at positions 3..5 land
    in rows 3, 0, 1)."""
    cache = jnp.zeros((1, 4, 1, 2))
    new = jnp.arange(6, dtype=jnp.float32).reshape(1, 3, 1, 2) + 1
    rows = jnp.asarray([[3, 0, 1]])  # (3 + arange(3)) % 4
    out = np.asarray(kv_cache.append_rows(cache, new, rows))
    np.testing.assert_array_equal(out[0, 3, 0], [1, 2])
    np.testing.assert_array_equal(out[0, 0, 0], [3, 4])
    np.testing.assert_array_equal(out[0, 1, 0], [5, 6])
    assert (out[0, 2] == 0).all()  # untouched


# -- apply_lm_cached: decode parity ------------------------------------------


def test_incremental_decode_matches_full_forward():
    """THE serving pin: prefill + one-token decode steps reproduce the
    full-forward apply_lm logits at EVERY position, tight tolerance."""
    B, T, C = 2, 24, 32
    params = _params(1)
    ds = synthesize_copy(num_train=B, num_test=B, seq_len=T,
                         vocab=SPEC.vocab, seed=2)
    tokens = jnp.asarray(ds.tokens)
    full = transformer.apply_lm(params, tokens, SPEC, attn_fn=_oracle_attn())
    ck, cv, cpos = _empty_cache(B, C)
    n = 9  # deliberately not a power of two
    outs = []
    lg, ck, cv, cpos = transformer.apply_lm_cached(
        params, tokens[:, :n], ck, cv, cpos, SPEC,
        start=jnp.zeros((B,), jnp.int32),
    )
    outs.append(lg)
    for t in range(n, T):
        lg, ck, cv, cpos = transformer.apply_lm_cached(
            params, tokens[:, t:t + 1], ck, cv, cpos, SPEC,
            start=jnp.full((B,), t, jnp.int32),
        )
        outs.append(lg)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               atol=2e-5, rtol=1e-4)


def test_rope_extrapolation_beyond_training_length():
    """RoPE is stateless in position: at offsets far past any training
    length (1e6+) the shard-consistency property still holds exactly,
    rotations stay norm-preserving, and prefill-vs-decode position
    handling agrees — apply_lm at a huge pos_offset == the cached path
    fed the same absolute positions (the decode-time extrapolation
    contract, ISSUE 2 satellite)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 2, 8))
    big = 1_000_000
    full = transformer.rope(x, big + jnp.arange(16), 10000.0)
    shard = transformer.rope(x[:, 8:], big + 8 + jnp.arange(8), 10000.0)
    np.testing.assert_allclose(np.asarray(full[:, 8:]), np.asarray(shard),
                               atol=1e-6)
    # Norm preservation per rotated pair: no blowup at extreme angles.
    pairs = np.asarray(full).reshape(2, 16, 2, 4, 2)
    base = np.asarray(x).reshape(2, 16, 2, 4, 2)
    np.testing.assert_allclose(
        np.linalg.norm(pairs, axis=-1), np.linalg.norm(base, axis=-1),
        atol=1e-5, rtol=1e-5,
    )

    # Prefill-vs-decode at the offset: teacher-forced apply_lm with
    # pos_offset=big == prefill + decode steps whose positions override
    # carries the same absolute positions (cache rows stay 0-based —
    # rows and positions are decoupled exactly for this).
    B, T, C = 1, 12, 16
    params = _params(3)
    tokens = jnp.asarray(
        synthesize_copy(num_train=B, num_test=B, seq_len=T,
                        vocab=SPEC.vocab, seed=4).tokens
    )
    full = transformer.apply_lm(params, tokens, SPEC,
                                attn_fn=_oracle_attn(), pos_offset=big)
    ck, cv, cpos = _empty_cache(B, C)
    n = 7
    pos = big + jnp.arange(T, dtype=jnp.int32)
    outs = []
    lg, ck, cv, cpos = transformer.apply_lm_cached(
        params, tokens[:, :n], ck, cv, cpos, SPEC,
        start=jnp.zeros((B,), jnp.int32), positions=pos[None, :n],
    )
    outs.append(lg)
    for t in range(n, T):
        lg, ck, cv, cpos = transformer.apply_lm_cached(
            params, tokens[:, t:t + 1], ck, cv, cpos, SPEC,
            start=jnp.full((B,), t, jnp.int32),
            positions=pos[None, t:t + 1],
        )
        outs.append(lg)
    inc = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               atol=2e-5, rtol=1e-4)


# -- the engine on its mesh --------------------------------------------------


@pytest.mark.parametrize("tp", [1, 2])
def test_engine_decode_parity(tp):
    """The compiled (prefill, decode) pair reproduces full-forward
    apply_lm logits at every position — tp=1 and tp=2 serving meshes
    (acceptance pin). Greedy, so tokens are argmax-checkable too."""
    C = 32
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=C,
                                      tensor_parallel=tp))
    params = transformer.init_lm_params(jax.random.PRNGKey(ServeConfig().seed),
                                        SPEC)
    prompt = synthesize_prompts(num=1, min_len=11, max_len=11,
                                vocab=SPEC.vocab, seed=5)[0]
    p = len(prompt)
    tok, prefill_logits = eng.prefill(prompt, slot=1, request_id=7,
                                      want_logits=True)
    seq = list(prompt) + [tok]
    logits_inc = [prefill_logits]
    last = np.zeros(2, np.int32)
    lengths = np.zeros(2, np.int32)
    ids = np.zeros(2, np.int32)
    active = np.zeros(2, bool)
    for step in range(6):
        last[1], lengths[1], ids[1], active[1] = seq[-1], len(seq) - 1, 7, True
        nxt, lg = eng.decode(last, lengths, ids, active, want_logits=True)
        logits_inc.append(lg[1:2])
        seq.append(int(nxt[1]))
    inc = np.concatenate(logits_inc, axis=0)  # [p + 6, V]
    full = transformer.apply_lm(
        params, jnp.asarray(np.asarray(seq[:-1])[None]), SPEC,
        attn_fn=_oracle_attn(),
    )[0]
    np.testing.assert_allclose(inc, np.asarray(full), atol=2e-5, rtol=1e-4)
    # Greedy decode tokens are the full-forward argmaxes.
    np.testing.assert_array_equal(
        np.asarray(seq[p:]), np.argmax(np.asarray(full)[p - 1:], axis=-1)
    )


def test_continuous_batching_matches_isolated_decode():
    """Acceptance pin: staggered arrivals + slot churn (5 requests over
    2 slots) yield bit-identical tokens to each request decoded alone —
    greedy AND seeded temperature/top-k sampling."""
    prompts = synthesize_prompts(num=5, min_len=3, max_len=9,
                                 vocab=SPEC.vocab, seed=6)
    for kw in (dict(temperature=0.0),
               dict(temperature=0.8, top_k=8, seed=11)):
        cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, **kw)
        eng = InferenceEngine(cfg)
        sched = Scheduler(eng)
        reqs = [Request(id=i, prompt=p, max_new_tokens=5, arrival=i % 3)
                for i, p in enumerate(prompts)]
        done, stats = sched.run(reqs)
        assert sorted(done) == list(range(5))
        assert stats.decode_tokens > 0 and stats.latency.p99_ms > 0
        for r in reqs:
            eng.reset()  # same engine (no recompile), fresh cache
            alone, _ = sched.run([Request(id=r.id, prompt=r.prompt,
                                          max_new_tokens=5)])
            assert alone[r.id].tokens == done[r.id].tokens, (kw, r.id)


def test_scheduler_slot_reuse_and_validation():
    """Slot eviction/reuse leaks nothing (more requests than slots, all
    complete with the right lengths); bad requests are rejected up
    front; eos stops a sequence early."""
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=16))
    sched = Scheduler(eng)
    prompts = synthesize_prompts(num=3, min_len=4, max_len=6,
                                 vocab=SPEC.vocab, seed=7)
    done, _ = sched.run([Request(id=i, prompt=p, max_new_tokens=4)
                         for i, p in enumerate(prompts)])
    assert all(len(done[i].tokens) == 4 for i in range(3))
    with pytest.raises(ValueError, match="capacity"):
        sched.run([Request(id=0, prompt=prompts[0], max_new_tokens=99)])
    with pytest.raises(ValueError, match="duplicate"):
        sched.run([Request(id=1, prompt=prompts[0], max_new_tokens=1),
                   Request(id=1, prompt=prompts[1], max_new_tokens=1)])
    # eos: greedy decode is deterministic — find the first greedy token
    # and declare it eos; the run must stop at 1 generated token.
    done, _ = sched.run([Request(id=5, prompt=prompts[0],
                                 max_new_tokens=4)])
    eos = done[5].tokens[0]
    stopped, _ = Scheduler(eng, eos_id=eos).run(
        [Request(id=6, prompt=prompts[0], max_new_tokens=4)]
    )
    assert stopped[6].tokens == [eos]


def test_scheduler_rejects_oversized_prompt_before_any_admit():
    """One prompt longer than the cache capacity among valid requests
    fails the WHOLE submit with a ValueError naming that request id —
    at validation time, before any slot prefills — never mid-run after
    other slots were admitted. The engine keeps no partial state: the
    same valid requests then serve normally on the same engine."""
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=16))
    sched = Scheduler(eng)
    valid = synthesize_prompts(num=2, min_len=4, max_len=6,
                               vocab=SPEC.vocab, seed=11)
    oversized = np.zeros(17, np.int32)  # 17 > capacity 16
    reqs = [
        Request(id=0, prompt=valid[0], max_new_tokens=2),
        Request(id=7, prompt=oversized, max_new_tokens=2),
        Request(id=2, prompt=valid[1], max_new_tokens=2),
    ]
    with pytest.raises(ValueError, match=r"request 7.*exceeds cache"):
        sched.run(reqs)
    # No partial admission happened: the valid pair still serves, and
    # its outputs equal a fresh engine's (nothing leaked into the cache).
    done, _ = sched.run([reqs[0], reqs[2]])
    assert sorted(done) == [0, 2]
    fresh = Scheduler(InferenceEngine(
        ServeConfig(spec=SPEC, slots=2, capacity=16)
    ))
    done2, _ = fresh.run([reqs[0], reqs[2]])
    assert {i: done[i].tokens for i in done} == \
        {i: done2[i].tokens for i in done2}


def test_params_only_checkpoint_load_from_zero1_tp(tmp_path):
    """ISSUE 2 satellite: a checkpoint written by SeqTrainer with
    --zero1 --tensor-parallel (the hybrid optimizer's save path) loads
    params-only into serving meshes (tp=1 AND tp=2 — re-sharding on
    load), and the served logits match full-forward apply_lm under the
    trained params."""
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
    from ddl_tpu.utils.checkpoint import load_params

    ds = synthesize_copy(num_train=32, num_test=8, seq_len=16,
                         vocab=SPEC.vocab, seed=8)
    ckdir = str(tmp_path / "ck")
    SeqTrainer(
        SeqConfig(epochs=1, batch_size=16, eval_every=0, num_workers=2,
                  data_parallel=2, tensor_parallel=2, zero1=True,
                  scheme="ring", spec=SPEC, seed=9),
        ds,
    ).train(log=lambda s: None, checkpoint_dir=ckdir)
    path = str(tmp_path / "ck" / "ckpt.npz")

    template = jax.eval_shape(
        lambda: transformer.init_lm_params(jax.random.PRNGKey(0), SPEC)
    )
    host, step, _ = load_params(path, template)
    assert step == 2  # the epoch-end save recorded its global batch
    prompt = synthesize_prompts(num=1, min_len=8, max_len=8,
                                vocab=SPEC.vocab, seed=10)[0]
    full = transformer.apply_lm(host, jnp.asarray(prompt[None]), SPEC,
                                attn_fn=_oracle_attn())[0]
    for tp in (1, 2):
        eng = InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=16,
                                          tensor_parallel=tp))
        eng.load_params(path)
        _, logits = eng.prefill(prompt, slot=0, request_id=0,
                                want_logits=True)
        np.testing.assert_allclose(logits, np.asarray(full),
                                   atol=2e-5, rtol=1e-4, err_msg=f"tp={tp}")

    # The params-only contract: the same load works when optimizer state
    # is ABSENT entirely (a bare params export).
    from ddl_tpu.utils.checkpoint import save_checkpoint

    bare = str(tmp_path / "params_only.npz")
    save_checkpoint(bare, host)
    again, _, _ = load_params(bare, template)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prompt_generator_contract():
    """synthesize_prompts: deterministic per seed, variable lengths in
    range, BOS-led, payload within vocab (ISSUE 2 satellite)."""
    a = synthesize_prompts(num=12, min_len=3, max_len=20, vocab=32, seed=3)
    b = synthesize_prompts(num=12, min_len=3, max_len=20, vocab=32, seed=3)
    c = synthesize_prompts(num=12, min_len=3, max_len=20, vocab=32, seed=4)
    assert len(a) == 12
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))
    lens = {len(x) for x in a}
    assert lens <= set(range(3, 21)) and len(lens) > 1
    for x in a:
        assert x.dtype == np.int32 and x[0] == 0
        assert (x[1:] >= 1).all() and (x[1:] < 32).all()
    with pytest.raises(ValueError, match="min_len"):
        synthesize_prompts(min_len=5, max_len=4)


# -- prefix cache + chunked prefill (ISSUE 4) --------------------------------


def test_kv_copy_prefix_op():
    """ops.kv_cache.copy_prefix: rows [0, n) along the axis take src,
    the rest keep dst — for both the k/v layout ([L, 1, C, H, D],
    axis=2) and a flat [B, C] layout (axis=1)."""
    src = jnp.arange(24, dtype=jnp.float32).reshape(1, 1, 6, 2, 2) + 100
    dst = jnp.arange(24, dtype=jnp.float32).reshape(1, 1, 6, 2, 2)
    out = np.asarray(kv_cache.copy_prefix(dst, src, jnp.int32(4), axis=2))
    np.testing.assert_array_equal(out[0, 0, :4], np.asarray(src)[0, 0, :4])
    np.testing.assert_array_equal(out[0, 0, 4:], np.asarray(dst)[0, 0, 4:])
    flat_src = jnp.ones((2, 5))
    flat_dst = jnp.zeros((2, 5))
    out = np.asarray(kv_cache.copy_prefix(flat_dst, flat_src, jnp.int32(2)))
    np.testing.assert_array_equal(out[:, :2], 1.0)
    np.testing.assert_array_equal(out[:, 2:], 0.0)


def test_kv_copy_prefix_edge_n_zero_and_full_capacity():
    """ISSUE 7 satellite: the copy-range boundaries, exercised directly
    (previously only reached through the engine). n=0 copies NOTHING
    (dst bit-unchanged — an empty hit is a no-op by construction);
    n=capacity copies EVERYTHING (dst == src bitwise — a full-cache hit
    leaves no seam); both ends also hold for the traced-scalar form the
    compiled copy programs use."""
    key = jax.random.PRNGKey(20)
    src = jax.random.normal(key, (1, 1, 6, 2, 8))
    dst = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 6, 2, 8))
    out0 = np.asarray(kv_cache.copy_prefix(dst, src, jnp.int32(0), axis=2))
    np.testing.assert_array_equal(out0, np.asarray(dst))
    out_full = np.asarray(
        kv_cache.copy_prefix(dst, src, jnp.int32(6), axis=2)
    )
    np.testing.assert_array_equal(out_full, np.asarray(src))
    # Same answers under jit with a TRACED n — the compiled-program
    # form (one program covers every hit length, 0 and capacity
    # included).
    jitted = jax.jit(lambda d, s, n: kv_cache.copy_prefix(d, s, n, axis=2))
    np.testing.assert_array_equal(
        np.asarray(jitted(dst, src, jnp.int32(0))), np.asarray(dst)
    )
    np.testing.assert_array_equal(
        np.asarray(jitted(dst, src, jnp.int32(6))), np.asarray(src)
    )
    # n beyond the axis saturates at "everything" (mask arange < n).
    np.testing.assert_array_equal(
        np.asarray(jitted(dst, src, jnp.int32(99))), np.asarray(src)
    )


def test_kv_attend_all_pad_rows_is_finite_and_length_stable():
    """ISSUE 7 satellite: attend over a cache of ONLY PAD_POS rows (a
    fresh slot / fresh page pool) stays FINITE — the all-masked softmax
    degrades to uniform weights over junk it then multiplies by exactly
    representable values, never NaN/Inf — and adding more masked
    padding never changes a valid query's output BITWISE (masked rows
    contribute exactly 0), which is the property the paged page-count
    buckets stand on (ops.kv_cache.gather_pages and the paged ≡
    contiguous pin in tests/test_serve_paged.py)."""
    key = jax.random.PRNGKey(21)
    q = jax.random.normal(key, (2, 3, 2, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, 2, 8))
    qpos = jnp.broadcast_to(jnp.arange(3), (2, 3))
    # All-PAD cache: nothing attendable, output must still be finite
    # (free slots and freshly admitted paged slots ride decode exactly
    # like this).
    all_pad = jnp.full((2, 16), PAD_POS)
    out = np.asarray(kv_cache.attend(q, k, v, qpos, all_pad))
    assert np.isfinite(out).all()
    # Length stability: valid rows + masked tail of DIFFERENT lengths
    # produce bitwise-identical outputs (the page-count bucket ladder's
    # correctness condition).
    kpos8 = jnp.where(jnp.arange(8) < 3, jnp.arange(8), PAD_POS)[None]
    kpos16 = jnp.where(jnp.arange(16) < 3, jnp.arange(16), PAD_POS)[None]
    a8 = np.asarray(kv_cache.attend(q[:1], k[:1, :8], v[:1, :8],
                                    qpos[:1], kpos8))
    a16 = np.asarray(kv_cache.attend(q[:1], k[:1], v[:1],
                                     qpos[:1], kpos16))
    np.testing.assert_array_equal(a8, a16)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_logits_exactly_equal_one_shot(chunk):
    """Acceptance pin: prefilling a prompt in fixed chunks (base
    offsets) produces the logits of the one-shot prefill of the same
    prompt, partial final chunk included, and the same sampled token:
    chunking cannot move the token stream. Through the one-shot's OWN
    program (every chunk forced to its bucket) the logits are EXACTLY
    equal — bitwise: chunking itself adds nothing. At a chunk's natural
    bucket (8 / 16 against the one-shot's 32) the programs differ in
    shape, and XLA on the CPU promises no bitwise equality across
    shapes: a one-shot prefill at bucket 64 is as far from the one at 32
    (1.19e-6, PR 30), so there the logits are held to 1e-5."""
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=64))
    prompt = synthesize_prompts(num=1, min_len=21, max_len=21,
                                vocab=SPEC.vocab, seed=14)[0]
    tok_full, logits_full = eng.prefill(prompt, slot=0, request_id=3,
                                        want_logits=True)

    def chunked(bucket):
        eng.reset()
        got = []
        for base in range(0, len(prompt), chunk):
            tok_last, lg = eng.prefill(prompt[base:base + chunk], slot=0,
                                       request_id=3, base=base,
                                       _bucket=bucket, want_logits=True)
            got.append(lg)
        assert tok_last == tok_full  # same sampled element p
        return np.concatenate(got, axis=0)

    np.testing.assert_array_equal(
        chunked(eng.prefill_bucket(len(prompt))), logits_full)
    np.testing.assert_allclose(chunked(None), logits_full, rtol=0, atol=1e-5)


def test_prefix_copy_then_tail_prefill_matches_full_prefill():
    """The prefix-reuse device path: register prompt A's rows in the
    pool, admit prompt B (sharing A's first tokens) as copy + tail
    prefill — B's tail logits and first sampled token are EXACTLY the
    full-prefill values (copied rows are bit-identical to recomputed
    rows)."""
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=64,
                                      prefix_slots=1))
    fam = synthesize_shared_prefix_prompts(
        n_families=1, per_family=2, prefix_len=12, tail_min=4, tail_max=4,
        vocab=SPEC.vocab, seed=15,
    )
    a, b = fam[0], fam[1]
    eng.prefill(a, slot=0, request_id=0)
    assert eng.prefix_store(a, 0)
    entry, hit = eng.prefix.match(b)
    assert entry >= 0 and hit >= 12  # at least the family prefix
    hit = min(hit, len(b) - 1)
    # Reference: full prefill of b on a FRESH engine state.
    ref_eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=64))
    tok_ref, logits_ref = ref_eng.prefill(b, slot=1, request_id=7,
                                          want_logits=True)
    # Reused path: copy the hit rows into slot 1, prefill only the tail.
    eng.prefix_fetch(entry, hit, 1)
    tok, tail_logits = eng.prefill(b[hit:], slot=1, request_id=7, base=hit,
                                   want_logits=True)
    np.testing.assert_array_equal(tail_logits, logits_ref[hit:])
    assert tok == tok_ref
    eng.prefix_release(entry)


def test_prefix_pool_lru_eviction_honors_refcounts():
    """ISSUE 4 satellite pin, on the host index directly: a shared
    prefix with a live reader survives pool pressure (LRU skips pinned
    entries — a full pool of pinned entries SKIPS registration rather
    than evicting); releasing the last reader makes it evictable
    again."""
    idx = PrefixIndex(2)
    e0, s0 = idx.insert([0, 1, 2, 3])
    e1, s1 = idx.insert([0, 5, 6, 7])
    assert {s0, s1} == {0, 1} and len(idx) == 2
    idx.acquire(e0)  # a live request attends e0's rows
    idx.touch(e1)  # e1 is MRU, e0 strictly LRU — refcount must win
    # match() is PURE: it never refreshes LRU stamps (a sub-threshold
    # BOS-only match must not keep a dead entry recent).
    before = idx.entry(e0).last_used
    idx.match([0, 1, 2, 3, 4])
    assert idx.entry(e0).last_used == before
    got = idx.insert([0, 8, 8])  # pressure: must NOT evict pinned e0
    assert got is not None
    e2, _ = got
    assert idx.entry(e0).tokens == (0, 1, 2, 3)  # pinned e0 survives
    assert idx.evictions == 1  # e1 (LRU among ref-0) paid instead
    with pytest.raises(KeyError):
        idx.entry(e1)
    idx.acquire(e2)
    # Both residents pinned: registration is skipped, never an eviction.
    assert idx.insert([0, 9, 9]) is None
    assert idx.skipped_full == 1
    # Releasing the LAST reader frees e0 for the next insertion.
    idx.release(e0)
    got = idx.insert([0, 9, 9])
    assert got is not None
    with pytest.raises(KeyError):
        idx.entry(e0)
    # Matching follows the trie: deepest live coverage wins.
    eid, depth = idx.match([0, 9, 9, 1])
    assert eid == got[0] and depth == 3
    # Releasing an entry nobody holds is a bookkeeping bug, loudly.
    with pytest.raises(ValueError, match="no readers"):
        idx.release(eid)


@pytest.mark.parametrize("tp", [1, 2])
def test_prefix_cache_scheduler_determinism(tp):
    """THE ISSUE 4 acceptance pin: a staggered-arrival shared-prefix
    workload served with the prefix cache ON yields BIT-IDENTICAL
    per-request tokens to the cache-off scheduler run — tp=1 and tp=2 —
    while actually hitting (the stats prove reuse happened, so the pin
    is not vacuous)."""
    prompts = synthesize_shared_prefix_prompts(
        n_families=2, per_family=3, prefix_len=12, tail_min=2, tail_max=6,
        vocab=SPEC.vocab, seed=16,
    )
    reqs = [Request(id=i, prompt=p, max_new_tokens=6, arrival=i % 3)
            for i, p in enumerate(prompts)]
    off = Scheduler(InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=64, tensor_parallel=tp,
    ))).run(reqs)[0]
    on_eng = InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=64, tensor_parallel=tp, prefix_slots=2,
    ))
    on, stats = Scheduler(on_eng).run(reqs)
    assert stats.prefix_hits > 0 and stats.prefill_tokens_saved > 0
    assert stats.prefix_lookups == len(reqs)
    assert 0.0 < stats.prefix_hit_rate <= 1.0
    assert stats.ttft.steps == len(reqs) and stats.ttft.p95_ms > 0
    for r in reqs:
        assert on[r.id].tokens == off[r.id].tokens, (tp, r.id)


def test_chunked_prefill_scheduler_determinism_and_stats():
    """Chunked prefill + per-tick budget (and the prefix cache on top)
    cannot move any request's tokens — greedy AND seeded sampling —
    and the inter-token-latency distribution is populated (the metric
    chunking exists to bound)."""
    prompts = synthesize_shared_prefix_prompts(
        n_families=2, per_family=3, prefix_len=12, tail_min=2, tail_max=6,
        vocab=SPEC.vocab, seed=17,
    )
    reqs = [Request(id=i, prompt=p, max_new_tokens=5, arrival=i % 2)
            for i, p in enumerate(prompts)]
    for kw in (dict(temperature=0.0),
               dict(temperature=0.9, top_k=8, seed=12)):
        off = Scheduler(InferenceEngine(ServeConfig(
            spec=SPEC, slots=2, capacity=64, **kw,
        ))).run(reqs)[0]
        on, stats = Scheduler(InferenceEngine(ServeConfig(
            spec=SPEC, slots=2, capacity=64, prefill_chunk=8,
            prefill_budget=8, prefix_slots=2, **kw,
        ))).run(reqs)
        assert stats.itl.steps > 0
        for r in reqs:
            assert on[r.id].tokens == off[r.id].tokens, (kw, r.id)


def test_scheduler_allow_window_opt_in():
    """ISSUE 4 satellite: prompt + max_new_tokens beyond capacity is
    rejected at submit naming the request — the ring would silently
    wrap into sliding-window attention mid-generation — UNLESS the
    caller passes allow_window=True, in which case the run completes
    with the full token count (the window semantics are opt-in, tested
    here end to end: resident length is capped at capacity while
    absolute positions keep growing)."""
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=16))
    prompt = synthesize_prompts(num=1, min_len=6, max_len=6,
                                vocab=SPEC.vocab, seed=18)[0]
    with pytest.raises(ValueError, match=r"request 9.*capacity 16"):
        Scheduler(eng).run([Request(id=9, prompt=prompt,
                                    max_new_tokens=14)])
    done, _ = Scheduler(eng, allow_window=True).run(
        [Request(id=9, prompt=prompt, max_new_tokens=14)]
    )
    assert len(done[9].tokens) == 14  # 6 + 14 = 20 > 16: ring wrapped
    # Unchanged guard: the WINDOW escape hatch never admits a prompt
    # longer than the cache itself.
    with pytest.raises(ValueError, match=r"request 3.*exceeds cache"):
        Scheduler(eng, allow_window=True).run(
            [Request(id=3, prompt=np.zeros(17, np.int32),
                     max_new_tokens=1)]
        )


def test_engine_rejects_bad_prefix_and_chunk_configs():
    """Config validation fails fast with the fix in the message: odd
    chunk sizes, budgets without chunking, budgets below the chunk,
    negative pool widths."""
    for bad in (dict(prefill_chunk=12), dict(prefill_chunk=4),
                dict(prefill_budget=16), dict(prefix_slots=-1),
                dict(prefill_chunk=16, prefill_budget=8)):
        with pytest.raises(ValueError):
            InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=32,
                                        **bad))


def test_scheduler_pressure_probe_matches_registry_gauges():
    """ISSUE 8 satellite: ``Scheduler.pressure()`` is pinned EQUAL to
    the per-tick registry gauges (occupied/active slots, queue depth,
    free pages, prefix-pool residency) after every tick of an
    externally-driven run — the router reads load through one probe,
    never private state — and the begin/submit/tick/collect form
    produces exactly ``run``'s completions (run IS that sequence)."""
    from ddl_tpu.obs import MetricRegistry

    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=8, prefix_slots=2)
    eng = InferenceEngine(cfg)
    reg = MetricRegistry()
    sched = Scheduler(eng, registry=reg)
    prompts = synthesize_prompts(num=4, min_len=4, max_len=7,
                                 vocab=SPEC.vocab, seed=21)
    reqs = [Request(id=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    sched.begin()
    for r in reqs:
        sched.submit(r)
    pr = sched.pressure()
    assert pr.waiting_eligible == 4 and pr.occupied_slots == 0
    assert pr.pending_total == 4 and pr.outstanding == 4
    assert pr.pages_free == eng.num_pages
    ticks = 0
    while not sched.idle:
        sched.tick()
        ticks += 1
        pr = sched.pressure()
        assert pr.occupied_slots == reg.gauge("serve_occupied_slots").value()
        assert pr.active_slots == reg.gauge("serve_active_slots").value()
        assert pr.waiting_eligible == reg.gauge("serve_queue_depth").value()
        assert pr.pages_free == reg.gauge("serve_kv_pages_free").value()
        assert pr.prefix_entries == \
            reg.gauge("serve_prefix_pool_entries").value()
        assert pr.pages_available <= pr.pages_free
    done, stats = sched.collect()
    assert ticks > 0 and sorted(done) == [0, 1, 2, 3]
    assert stats.decode_tokens > 0
    # The probe is quiescent again, and run() on a fresh engine (same
    # machinery, one call) reproduces the driven run's tokens.
    assert sched.pressure().occupied_slots == 0
    fresh = Scheduler(InferenceEngine(cfg))
    done2, _ = fresh.run(reqs)
    assert {i: done[i].tokens for i in done} == \
        {i: done2[i].tokens for i in done2}
    # Lifecycle guards: tick/collect need an armed run; begin can't
    # stack; release() disarms an aborted run.
    with pytest.raises(RuntimeError, match="begin"):
        sched.tick()
    sched.begin()
    with pytest.raises(RuntimeError, match="already armed"):
        sched.begin()
    sched.release()
    sched.begin()
    sched.release()


# -- long sweeps (excluded from tier-1 via -m 'not slow') --------------------


@pytest.mark.slow
def test_continuous_batching_sweep_slow():
    """The wide grid: arrival patterns x sampling configs x slot widths,
    all pinned against isolated decode — the exhaustive version of the
    fast smoke above."""
    prompts = synthesize_prompts(num=8, min_len=3, max_len=14,
                                 vocab=SPEC.vocab, seed=12)
    for slots in (2, 3):
        for kw in (dict(temperature=0.0), dict(temperature=1.2, seed=5),
                   dict(temperature=0.6, top_k=4, seed=6)):
            eng = InferenceEngine(
                ServeConfig(spec=SPEC, slots=slots, capacity=64, **kw)
            )
            sched = Scheduler(eng)
            reqs = [Request(id=i, prompt=p, max_new_tokens=3 + i % 5,
                            arrival=(i * 2) % 5)
                    for i, p in enumerate(prompts)]
            done, _ = sched.run(reqs)
            for r in reqs:
                eng.reset()
                alone, _ = sched.run([Request(
                    id=r.id, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens,
                )])
                assert alone[r.id].tokens == done[r.id].tokens, (
                    slots, kw, r.id
                )


@pytest.mark.slow
def test_engine_tp2_long_generation_slow():
    """tp=2 decode far past the prompt (40 steps, capacity 64): logits
    stay pinned to full-forward at every generated position."""
    C = 64
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=C,
                                      tensor_parallel=2))
    params = transformer.init_lm_params(
        jax.random.PRNGKey(ServeConfig().seed), SPEC
    )
    prompt = synthesize_prompts(num=1, min_len=6, max_len=6,
                                vocab=SPEC.vocab, seed=13)[0]
    tok, _ = eng.prefill(prompt, slot=0, request_id=1)
    seq = list(prompt) + [tok]
    for _ in range(40):
        nxt, _ = eng.decode(
            np.asarray([seq[-1]], np.int32),
            np.asarray([len(seq) - 1], np.int32),
            np.asarray([1], np.int32), np.asarray([True]),
        )
        seq.append(int(nxt[0]))
    full = transformer.apply_lm(
        params, jnp.asarray(np.asarray(seq[:-1])[None]), SPEC,
        attn_fn=_oracle_attn(),
    )[0]
    np.testing.assert_array_equal(
        np.asarray(seq[len(prompt):]),
        np.argmax(np.asarray(full)[len(prompt) - 1:], axis=-1),
    )
