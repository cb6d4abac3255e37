"""Paged KV cache (ISSUE 7): block-table attention, zero-copy
refcounted prefix sharing, pooled serve capacity.

The oracle chain: the CONTIGUOUS slot-major engine (PR 2-6, retained
behind ``page_size=0``) is the bit-exactness reference — the paged
engine must reproduce its tokens AND per-step logits bitwise through
the whole serving stack (staggered arrivals, prefix sharing, chunked
prefill, deadline eviction), at tp=1 and tp=2. On top of parity, the
paged-only contracts: a prefix hit moves zero K/V rows beyond the one
copy-on-write partial tail page (the ``page_copies`` counter and the
``prefix_map`` trace events assert it), refcounted pages reclaim when
their last holder finishes (pool reusable), and admission pools
capacity across slots ("enough free pages" — a long-tail mix admits
under a pool the slot-major layout must worst-case-reserve).

Every scheduler-driving test stays inside the tier-1 audit budget
(tests/test_markers.py: <= 64 estimated tokens, <= 2 topologies).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.data.lm import (
    synthesize_longtail_prompts,
    synthesize_prompts,
    synthesize_shared_prefix_prompts,
)
from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs.trace import Tracer
from ddl_tpu.ops import kv_cache
from ddl_tpu.ops.kv_cache import PAD_POS
from ddl_tpu.serve import (
    InferenceEngine,
    Request,
    Scheduler,
    ServeConfig,
)

SPEC = TINY_SPEC


# -- ops: the block-table primitives ------------------------------------------


def test_table_rows_gather_and_write_roundtrip():
    """The paged device contract end to end at the op level: logical
    rows flatten through the table (unmapped/out-of-reach -> OOB, so
    writes DROP), gathers return pages in logical order, and positions
    travel with rows (PAD_POS where the table is unmapped)."""
    ps, P = 4, 6
    pool = jnp.zeros((P, ps, 3))
    pos = jnp.full((P, ps), PAD_POS)
    # Slot 0 owns pages [2, 0]; slot 1 owns [5]; second entries unmapped.
    table = jnp.asarray([[2, 0], [5, -1]], jnp.int32)
    logical = jnp.asarray([[0, 1, 5], [2, 9, 4]], jnp.int32)
    flat = kv_cache.table_rows(table, logical, ps, P)
    # slot 0: rows 0,1 -> page 2 offsets 0,1 (flat 8,9); row 5 -> page 0
    # offset 1 (flat 1). slot 1: row 2 -> page 5 offset 2 (flat 22);
    # row 9 is beyond the 2-page reach -> drop; row 4 -> page index 1 is
    # UNMAPPED (-1) -> drop.
    np.testing.assert_array_equal(np.asarray(flat),
                                  [[8, 9, 1], [22, 24, 24]])
    new = jnp.arange(2 * 3 * 3, dtype=jnp.float32).reshape(2, 3, 3) + 1
    out = kv_cache.write_rows_flat(pool, new, flat)
    np.testing.assert_array_equal(np.asarray(out)[2, 0], [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(out)[2, 1], [4, 5, 6])
    np.testing.assert_array_equal(np.asarray(out)[0, 1], [7, 8, 9])
    np.testing.assert_array_equal(np.asarray(out)[5, 2], [10, 11, 12])
    # Only the four mapped writes landed; the two dropped rows of slot 1
    # left no trace anywhere in the pool.
    assert float(jnp.abs(out).sum()) == sum(
        float(jnp.abs(new[b, t]).sum()) for b, t in
        [(0, 0), (0, 1), (0, 2), (1, 0)]
    )
    # Gather returns slot 0's pages in TABLE order: page 2 then page 0.
    g = kv_cache.gather_pages(out, table)
    assert g.shape == (2, 2 * ps, 3)
    np.testing.assert_array_equal(np.asarray(g)[0, 0], [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(g)[0, ps + 1], [7, 8, 9])
    # Positions: written rows carry their values, unmapped pages PAD.
    pos2 = kv_cache.write_rows_flat(
        pos, jnp.asarray([[0, 1, 5], [2, 9, 4]]), flat
    )
    kpos = kv_cache.table_positions(pos2, table)
    assert int(kpos[0, 0]) == 0 and int(kpos[0, 1]) == 1
    assert int(kpos[0, ps + 1]) == 5
    assert int(kpos[1, 2]) == 2
    assert (np.asarray(kpos)[1, ps:] == PAD_POS).all()  # unmapped page


# -- the stacked pool is written in place (ISSUE 29) --------------------------


def _paged_forward_per_layer(params, tokens, pools, pool_pos, table, spec,
                             *, positions, flat_rows):
    """``apply_lm_paged`` as it stood before ISSUE 29, kept as the
    oracle: layer ``i``'s pool is taken out of the stack
    (``pool[i]``), written and gathered as a one-layer pool, and put
    back whole (``.at[i].set``). ``pools`` is ``(k, v)`` or, int8,
    ``(k, v, k_scale, v_scale)``; returns ``(logits, pools, pos)``."""
    from ddl_tpu.models.transformer import _layernorm, rope

    quantized = len(pools) == 4
    pools = list(pools)
    h = params["embed"][tokens]
    b, t, _ = h.shape
    pool_pos = kv_cache.write_rows_flat(
        pool_pos, positions.astype(pool_pos.dtype), flat_rows)
    k_pos = kv_cache.table_positions(pool_pos, table)
    heads = lambda a: a.reshape(b, t, -1, spec.head_dim)
    for i, blk in enumerate(params["blocks"]):
        x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
        q = rope(heads(x @ blk["wq"]), positions, spec.rope_base)
        k = rope(heads(x @ blk["wk"]), positions, spec.rope_base)
        v = heads(x @ blk["wv"])
        if quantized:
            (kq, ks), (vq, vs) = (kv_cache.quantize_rows(k),
                                  kv_cache.quantize_rows(v))
            fresh = (kq, vq, ks, vs)
        else:
            fresh = (k.astype(pools[0].dtype), v.astype(pools[1].dtype))
        layer = [kv_cache.write_rows_flat(p[i], f, flat_rows)
                 for p, f in zip(pools, fresh)]
        pools = [p.at[i].set(c) for p, c in zip(pools, layer)]
        views = [kv_cache.gather_pages(c, table) for c in layer]
        if quantized:
            k_view = kv_cache.dequantize_rows(views[0], views[2], q.dtype)
            v_view = kv_cache.dequantize_rows(views[1], views[3], q.dtype)
        else:
            k_view, v_view = (c.astype(q.dtype) for c in views)
        a = kv_cache.attend(q, k_view, v_view, positions, k_pos)
        h = h + a.reshape(b, t, -1) @ blk["wo"]
        x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        h = h + jax.nn.gelu(x @ blk["w1"] + blk["b1"]) @ blk["w2"] + blk["b2"]
    h = _layernorm(h, params["lnf_g"], params["lnf_b"])
    return (h @ params["head"]).astype(jnp.float32), tuple(pools), pool_pos


@pytest.mark.parametrize("step", ["padded_prefill_tail",
                                  "inactive_decode_slots"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_dropped_writes_stay_dropped_under_the_layer_offset(kv, step):
    """The in-place write of the stacked pool (layer ``i`` at flat row
    ``i * pages * page + flat``) keeps the drop discipline: a padded
    prefill tail and inactive decode slots, whose rows ``table_rows``
    maps to ``pages * page``, touch NO row of ANY layer — row 0 of
    layer ``i + 1``, where a naive offset would put them, above all.
    Every row of every leaf other than the rows written is bitwise as
    it was, and logits, pools and positions are bitwise what taking
    ``pool[i]`` out and putting it back whole gives."""
    from ddl_tpu.models.transformer import (LMSpec, apply_lm_paged,
                                            init_lm_params)

    spec = LMSpec(vocab=32, d_model=32, num_heads=2, num_layers=3, d_ff=64)
    ps, P, L, H, D = 4, 6, spec.num_layers, spec.num_heads, spec.head_dim
    keys = jax.random.split(jax.random.PRNGKey(29), 6)
    params = init_lm_params(keys[0], spec)
    if kv == "int8":
        payload = lambda k: jax.random.randint(
            k, (L, P, ps, H, D), -127, 128, jnp.int8)
        scale = lambda k: jax.random.uniform(
            k, (L, P, ps, H), jnp.float32, 0.001, 0.02)
        pools = (payload(keys[1]), payload(keys[2]),
                 scale(keys[3]), scale(keys[4]))
    else:
        pools = tuple(jax.random.normal(k, (L, P, ps, H, D), jnp.bfloat16)
                      for k in keys[1:3])
    pos = jnp.full((P, ps), PAD_POS, jnp.int32)
    if step == "padded_prefill_tail":
        # One slot, a bucket of 8 holding 5 real tokens over pages
        # [2, 1]: rows 0-3 -> page 2, row 4 -> page 1; the three padded
        # rows carry logical row = reach and drop.
        table = jnp.asarray([[2, 1, -1, -1]], jnp.int32)
        real = jnp.arange(8) < 5
        positions = jnp.where(real, jnp.arange(8), PAD_POS)[None, :]
        logical = jnp.where(real, jnp.arange(8), 4 * ps)[None, :]
        tokens = jax.random.randint(keys[5], (1, 8), 0, spec.vocab)
        written = {8, 9, 10, 11, 4}
    else:
        # Three slots, the middle one inactive (no page mapped); the
        # others hold 5 and 2 tokens of history and write their next.
        table = jnp.asarray([[2, 1], [-1, -1], [4, -1]], jnp.int32)
        active = jnp.asarray([True, False, True])
        lengths = jnp.asarray([5, 0, 2], jnp.int32)
        positions = jnp.where(active, lengths, PAD_POS)[:, None]
        logical = jnp.where(active, lengths, 2 * ps)[:, None]
        tokens = jax.random.randint(keys[5], (3, 1), 0, spec.vocab)
        pos = pos.at[2, :].set(jnp.arange(4)).at[1, 0].set(4)
        pos = pos.at[4, :2].set(jnp.arange(2))
        written = {1 * ps + 1, 4 * ps + 2}
    flat = kv_cache.table_rows(table, logical, ps, P)
    dropped = int((np.asarray(flat) == P * ps).sum())
    assert dropped == (3 if step == "padded_prefill_tail" else 1)
    assert {int(r) for r in np.asarray(flat).ravel()} - {P * ps} == written

    scales = (dict(pool_k_scale=pools[2], pool_v_scale=pools[3])
              if kv == "int8" else {})
    out = apply_lm_paged(params, tokens, pools[0], pools[1], pos, table,
                         spec, positions=positions, flat_rows=flat, **scales)
    logits, new_pools, new_pos = out[0], out[1:3] + out[4:], out[3]
    want_logits, want_pools, want_pos = _paged_forward_per_layer(
        params, tokens, pools, pos, table, spec, positions=positions,
        flat_rows=flat)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    np.testing.assert_array_equal(np.asarray(new_pos), np.asarray(want_pos))
    untouched = np.asarray(sorted(set(range(P * ps)) - written))
    for leaf, (old, new, want) in enumerate(
            zip(pools, new_pools, want_pools)):
        old, new, want = (np.asarray(a).reshape((L, P * ps) + a.shape[3:])
                          for a in (old, new, want))
        assert new.dtype == old.dtype
        np.testing.assert_array_equal(new, want, err_msg=f"leaf {leaf}")
        np.testing.assert_array_equal(new[:, untouched], old[:, untouched],
                                      err_msg=f"leaf {leaf}")
        np.testing.assert_array_equal(new[:, 0], old[:, 0],
                                      err_msg=f"leaf {leaf}: row 0")
        # ...and the rows written did change, in every layer.
        for r in written:
            assert (new[:, r] != old[:, r]).reshape(L, -1).any(1).all(), (
                leaf, r)


# -- validation: loud ctor + loud submit (ISSUE 7 satellite) ------------------


def test_paged_engine_config_validation_both_directions():
    """Bad page geometry is a CONSTRUCTION error naming the fix (the
    PR 4/6 loud-ctor pattern): non-power-of-two page_size, num_pages
    without page_size, num_pages below slots, capacity not tiling into
    pages. The matching good configs construct (both directions)."""
    good = dict(spec=SPEC, slots=2, capacity=32)
    for bad, msg in (
        (dict(page_size=12), "power of two"),
        (dict(page_size=-8), "power of two"),
        (dict(num_pages=8), "requires page_size"),
        (dict(page_size=8, num_pages=1), "below slots"),
        (dict(page_size=8, num_pages=-1), "num_pages"),
        (dict(page_size=64), "multiple"),  # capacity 32 % 64 != 0
    ):
        with pytest.raises(ValueError, match=msg):
            InferenceEngine(ServeConfig(**good, **bad))
    eng = InferenceEngine(ServeConfig(**good, page_size=8, num_pages=2))
    assert eng.paged and eng.max_pages == 4 and eng.num_pages == 2
    # num_pages defaults to the slot-major envelope: slots * max_pages.
    eng = InferenceEngine(ServeConfig(**good, page_size=8))
    assert eng.num_pages == 2 * 4
    # page_size=0 stays the contiguous oracle.
    assert not InferenceEngine(ServeConfig(**good)).paged


def test_paged_scheduler_submit_validation_names_request():
    """Submit-time bounds name the offending request and the fix: the
    block-TABLE reach (capacity) and the whole-POOL reach (num_pages);
    allow_window has no paged semantics and is rejected at
    construction. The same requests admit once sized correctly."""
    eng = InferenceEngine(ServeConfig(spec=SPEC, slots=2, capacity=32,
                                      page_size=8, num_pages=5))
    sched = Scheduler(eng)
    ok = Request(id=1, prompt=np.zeros(6, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match=r"request 9.*block-table reach"):
        sched.run([ok, Request(id=9, prompt=np.zeros(20, np.int32),
                               max_new_tokens=20)])
    with pytest.raises(ValueError, match=r"request 8.*num_pages=3"):
        # 20 + 12 = 32 rows = 4 pages: INSIDE the table reach (4 pages)
        # but over a 3-page pool — the whole-pool bound fires, naming
        # the pool, not the table.
        Scheduler(InferenceEngine(ServeConfig(
            spec=SPEC, slots=2, capacity=32, page_size=8, num_pages=3,
        ))).run([Request(id=8, prompt=np.zeros(20, np.int32),
                         max_new_tokens=12)])
    with pytest.raises(ValueError, match="allow_window"):
        Scheduler(eng, allow_window=True)
    done, _ = sched.run([ok])
    assert done[1].status == "ok" and len(done[1].tokens) == 2


# -- THE acceptance pin: paged ≡ contiguous, bitwise --------------------------


def _capture_logits(eng):
    """Map ``(request_id, position) -> logits row`` for every logit the
    engine computes, by wrapping its host API (the scheduler drives the
    wrapped engine unchanged): a prefill block at ``base`` contributes
    rows for positions ``base..base+t-1``, a decode tick one row per
    ACTIVE slot at its current length. Position-keyed because prefix
    hit depths may legitimately DIFFER between layouts (paged entries
    register floor-to-page coverage), shifting chunk boundaries — the
    parity contract is that any logit row both layouts compute for the
    same (request, position) is the same row, bitwise. Decode keys also
    return separately: decode schedules must agree exactly."""
    rows: dict[tuple[int, int], np.ndarray] = {}
    decode_keys: set[tuple[int, int]] = set()
    orig_prefill, orig_decode = eng.prefill, eng.decode

    def prefill(prompt, **kw):
        tok, lg = orig_prefill(prompt, **kw, want_logits=True)
        base = kw.get("base", 0)
        for j in range(np.asarray(lg).shape[0]):
            rows[(kw["request_id"], base + j)] = np.asarray(lg)[j].copy()
        return tok, lg

    def decode(last, lengths, ids, active, **kw):
        nxt, lg = orig_decode(last, lengths, ids, active, **kw,
                              want_logits=True)
        for s in np.nonzero(np.asarray(active, bool))[0]:
            key = (int(ids[s]), int(lengths[s]))
            rows[key] = np.asarray(lg)[s].copy()
            decode_keys.add(key)
        return nxt, lg

    eng.prefill, eng.decode = prefill, decode
    return rows, decode_keys


@pytest.mark.parametrize("tp", [1, 2])
def test_paged_decode_bitwise_equals_contiguous(tp):
    """THE ISSUE 7 acceptance pin: the staggered shared-prefix workload
    with prefix sharing AND chunked prefill on, served by the paged
    engine, produces BIT-IDENTICAL per-request tokens and per-step
    logits to the contiguous oracle — tp=1 and tp=2 — while actually
    sharing (hits > 0, so the pin is not vacuous). Every decode tick's
    (request, position) is computed by BOTH layouts and agrees bitwise
    at whatever page-count bucket the paged engine ran; every prefill
    position computed by both agrees bitwise too (hit depths may differ
    — paged entries cover floor-to-page — so prefill key SETS may
    differ; the shared keys may not)."""
    prompts = synthesize_shared_prefix_prompts(
        n_families=2, per_family=3, prefix_len=12, tail_min=2, tail_max=6,
        vocab=SPEC.vocab, seed=16,
    )
    reqs = [Request(id=i, prompt=p, max_new_tokens=5, arrival=i % 3)
            for i, p in enumerate(prompts)]
    base = dict(spec=SPEC, slots=2, capacity=64, tensor_parallel=tp,
                prefix_slots=2, prefill_chunk=8, prefill_budget=8)
    ec = InferenceEngine(ServeConfig(**base))
    rows_c, dec_c = _capture_logits(ec)
    done_c, _ = Scheduler(ec).run(reqs)
    ep = InferenceEngine(ServeConfig(**base, page_size=8, num_pages=16))
    rows_p, dec_p = _capture_logits(ep)
    done_p, stats = Scheduler(ep).run(reqs)
    assert stats.prefix_hits > 0  # sharing actually happened
    for r in reqs:
        assert done_p[r.id].tokens == done_c[r.id].tokens, (tp, r.id)
    # Decode ticks agree exactly: same (request, position) schedule.
    assert dec_p == dec_c and dec_c
    common = set(rows_c) & set(rows_p)
    assert common >= dec_c  # every decode position is in both
    for key in sorted(common):
        np.testing.assert_array_equal(rows_c[key], rows_p[key],
                                      err_msg=str((tp, key)))


# -- zero-copy sharing + refcounted reclamation -------------------------------


def test_paged_prefix_hit_zero_copy_and_pool_reclaim():
    """Acceptance: a paged prefix hit moves NO K/V rows beyond the one
    copy-on-write partial tail page — asserted via the engine's
    copy-program counter AND the prefix_map trace events (copied_rows
    < page_size, page-aligned hits copy nothing) — and every page
    reclaims when its last holder lets go: slots release at completion,
    entries at eviction, after which the pool is whole and REUSABLE
    (the rerun reproduces the first run's tokens)."""
    prompts = synthesize_shared_prefix_prompts(
        n_families=2, per_family=3, prefix_len=16, tail_min=2, tail_max=6,
        vocab=SPEC.vocab, seed=7,
    )
    reqs = [Request(id=i, prompt=p, max_new_tokens=4, arrival=i % 2)
            for i, p in enumerate(prompts)]
    eng = InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=64, prefix_slots=2,
        page_size=8, num_pages=16,
    ))
    tracer = Tracer()
    done, stats = Scheduler(eng, tracer=tracer).run(reqs)
    assert stats.prefix_hits > 0
    maps = [r["attrs"] for r in tracer.records
            if r.get("name") == "prefix_map"]
    assert len(maps) == stats.prefix_hits
    for attrs in maps:
        # Zero copies beyond the partial tail page: page-aligned hits
        # copy nothing, unaligned ones exactly hit % page_size rows.
        assert attrs["copied_rows"] == attrs["rows"] % 8
        assert attrs["copied_rows"] < 8
    assert eng.page_copies == sum(1 for a in maps if a["copied_rows"])
    # No contiguous-style full-prefix copy program even exists on this
    # path; the only copies the run made are the tail pages above.
    comp = [r["attrs"] for r in tracer.records
            if r.get("name") == "complete"]
    assert comp and all(a["kv_pages_held"] >= 1 for a in comp)
    # All slots released; only prefix entries still hold pages, every
    # held page carries exactly the live references.
    assert (eng.table_len == 0).all()
    held = sum(len(set(e.pages)) for e in eng.prefix._entries.values())
    assert eng.pages.free == eng.num_pages - held
    assert (eng.pages.refs >= 0).all()
    # Evicting the (zero-ref) entries returns EVERY page: nothing leaks.
    assert eng.reclaim_pages(eng.num_pages)
    assert eng.pages.free == eng.num_pages
    # Pool reusable: the rerun (cold index again) replays identically.
    again, _ = Scheduler(eng).run(reqs)
    for r in reqs:
        assert again[r.id].tokens == done[r.id].tokens


def test_paged_pinned_pages_survive_reclaim_pressure():
    """The refcount half of reclamation, on the engine directly: pages
    mapped by a LIVE slot (and the entry it pinned) survive a full
    reclaim sweep — only zero-ref entries' pages free — and release
    order doesn't matter (slot then entry, or entry then slot)."""
    eng = InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=32, prefix_slots=2,
        page_size=8, num_pages=8,
    ))
    prompt = np.zeros(16, np.int32)
    eng.prefill(prompt, slot=0, request_id=0)
    assert eng.prefix_store(prompt, 0)  # donates pages 0,1 (zero-copy)
    assert eng.pages.shared == 2
    entry, hit = eng.prefix.match(prompt)
    eng.prefix_fetch(entry, 8, 1)  # page-aligned: zero copies
    assert eng.page_copies == 0
    assert eng.pages.refs[0] == 3  # slot 0 + entry + slot 1
    # Reclaim pressure frees nothing: the only entry is pinned.
    assert not eng.reclaim_pages(eng.num_pages)
    assert eng.prefix.skipped_full == 0  # reclaim, not registration
    eng.release_slot(1)
    eng.prefix_release(entry)
    # Entry now ZERO-REF but its pages are still mapped by live slot 0:
    # evicting it would free nothing — reclaim must leave it resident
    # (a fruitless eviction only burns future hits) and report failure.
    assert not eng.reclaim_pages(eng.num_pages)
    assert len(eng.prefix) == 1
    eng.release_slot(0)
    assert eng.pages.free == eng.num_pages - 2  # entry's 2 pages remain
    assert eng.reclaim_pages(eng.num_pages)  # now actually freeable
    assert eng.pages.free == eng.num_pages


# -- pooled capacity: admission is "enough free pages" ------------------------


def test_paged_pool_admission_defers_until_pages_free():
    """Capacity pooling admits by PAGES, not worst-case slots: a pool
    too small to co-host the head request waits (strict FIFO) and
    admits once a finishing request frees pages — the run completes
    with tokens bit-identical to a generous-pool run, and the deferral
    actually happened (the waiter's admission follows a completion)."""
    prompts = synthesize_longtail_prompts(
        num_short=2, num_long=1, short_min=6, short_max=10, long_len=24,
        long_prefix_len=1, vocab=SPEC.vocab, seed=3,
    )
    reqs = [Request(id=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    tight = InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=32, page_size=8, num_pages=5,
    ))
    sched = Scheduler(tight)
    # Warmup must survive a TIGHT pool too (its compile ladders cap
    # their page use; clone-run residue is reset away first).
    sched.warmup(reqs)
    done_t, _ = sched.run(reqs)
    roomy = InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=32, page_size=8, num_pages=8,
    ))
    done_r, _ = Scheduler(roomy).run(reqs)
    for r in reqs:
        assert done_t[r.id].status == "ok"
        assert done_t[r.id].tokens == done_r[r.id].tokens, r.id
    # The long request (4 pages of 5) could not co-reside with both
    # shorts: somebody was admitted only after another finished.
    starts = sorted(done_t[i].admitted_step for i in done_t)
    first_finish = min(done_t[i].finished_step for i in done_t)
    assert starts[-1] >= first_finish
    # The generous pool co-hosted freely: both slots filled at step 0.
    assert sorted(done_r[i].admitted_step for i in done_r)[1] == 0
    assert tight.pages.free == tight.num_pages  # nothing leaked


def test_paged_reclaim_evicting_the_matched_entry_is_safe():
    """Admission under page pressure may reclaim the very entry the
    pending request just matched (it was zero-ref — exactly what
    reclaim evicts). The scheduler must re-probe after reclaiming:
    fetching the ghost entry would KeyError and the reservation would
    be undersized. Constructed so the first reclaim evicts the matched
    family prefix AND the re-probed need forces a second reclaim —
    the request then admits as a full prefill with correct tokens."""
    ps = 4
    mk = lambda: InferenceEngine(ServeConfig(
        spec=SPEC, slots=2, capacity=20, prefix_slots=2,
        page_size=ps, num_pages=5,
    ))
    eng = mk()
    prompt_a = np.arange(8, dtype=np.int32) % SPEC.vocab
    prompt_a2 = (np.arange(4, dtype=np.int32) + 9) % SPEC.vocab
    sched = Scheduler(eng)
    sched.run([Request(id=0, prompt=prompt_a, max_new_tokens=2),
               Request(id=1, prompt=prompt_a2, max_new_tokens=2,
                       arrival=1)])
    assert len(eng.prefix) == 2  # both registered, 3 pages pinned
    assert eng.pages.available == 2
    # B shares A's full prompt: matches entry A (2 shared pages), but
    # needs 5 pages total -> need 3 > available 2 -> reclaim evicts the
    # MATCHED zero-ref entry A first (LRU), then A2 on the re-probed
    # round -> full prefill, 5 fresh pages.
    prompt_b = np.concatenate([prompt_a, prompt_a[1:2]]).astype(np.int32)
    done, stats = sched.run([Request(id=7, prompt=prompt_b,
                                     max_new_tokens=11)])
    assert done[7].status == "ok" and len(done[7].tokens) == 11
    assert len(eng.prefix) <= 1  # the old entries were reclaimed
    # Correctness: same tokens as a fresh engine with no cache history.
    fresh, _ = Scheduler(mk()).run([Request(id=7, prompt=prompt_b,
                                            max_new_tokens=11)])
    assert fresh[7].tokens == done[7].tokens


def test_paged_deadline_eviction_releases_pages_and_keeps_parity():
    """The deadline-eviction interaction (acceptance): a stalled
    request admitted onto the paged pool (pages reserved, prefix
    pinned) expires at its deadline — pages AND reservation return to
    the pool, refs release — while co-residents' tokens stay
    bit-identical to the contiguous oracle under the same fault, with
    chunked prefill on (the full ISSUE 6 x ISSUE 7 composition)."""
    from ddl_tpu.resilience.faults import FaultInjector, FaultSpec

    prompts = synthesize_shared_prefix_prompts(
        n_families=1, per_family=3, prefix_len=12, tail_min=2, tail_max=4,
        vocab=SPEC.vocab, seed=9,
    )
    reqs = [
        Request(id=0, prompt=prompts[0], max_new_tokens=4),
        Request(id=1, prompt=prompts[1], max_new_tokens=4, arrival=1,
                deadline_s=0.02),
        Request(id=2, prompt=prompts[2], max_new_tokens=4, arrival=1),
    ]
    outs = {}
    for paged in (0, 8):
        eng = InferenceEngine(ServeConfig(
            spec=SPEC, slots=2, capacity=64, prefix_slots=2,
            prefill_chunk=8, page_size=paged,
            num_pages=16 if paged else 0,
        ))
        inj = FaultInjector(FaultSpec(kind="stall", step=1))
        done, _ = Scheduler(eng, injector=inj).run(reqs)
        assert done[1].status == "deadline_exceeded"
        assert done[0].status == "ok" and done[2].status == "ok"
        outs[paged] = {i: done[i].tokens for i in done}
        if paged:
            # Eviction released the stalled slot's pages + reservation;
            # only prefix entries hold pages now.
            assert (eng.table_len == 0).all()
            assert eng.pages.reserved == 0
            assert eng.reclaim_pages(eng.num_pages)
            assert eng.pages.free == eng.num_pages
            # Pool reusable after eviction (the PR 6 contract, paged).
            again, _ = Scheduler(eng).run(
                [Request(id=3, prompt=prompts[1], max_new_tokens=2)]
            )
            assert again[3].status == "ok"
    assert outs[0] == outs[8]  # paged ≡ contiguous under eviction


def test_release_returns_pool_byte_whole_reservations_included():
    """ISSUE 13 satellite: aborting an armed run mid-flight — occupants
    decoding, admission reservations outstanding, a mid-prefill slot —
    returns the pool BYTE-WHOLE through ``Scheduler.release()``: every
    page back on the free list AND every reservation cancelled (the
    abort path used to sweep only occupied slots' mapped pages; a
    drained/aborted replica must hand back promised-not-yet-mapped
    capacity too). The engine is then fully reusable."""
    eng = InferenceEngine(ServeConfig(
        spec=SPEC, slots=3, capacity=32, page_size=8, num_pages=8,
        prefill_chunk=8,
    ))
    prompts = synthesize_prompts(num=3, min_len=6, max_len=12,
                                 vocab=SPEC.vocab, seed=4)
    sched = Scheduler(eng)
    sched.begin()
    for i, p in enumerate(prompts):
        sched.submit(Request(id=i, prompt=p, max_new_tokens=12))
    for _ in range(2):
        sched.tick()
    # Mid-flight: pages mapped AND reservations outstanding.
    assert eng.pages.free < eng.num_pages
    assert eng.pages.reserved > 0
    # The fixed gap: a reservation on a slot with NO occupant (an
    # admission/adopt interrupted between reserve and install) — the
    # occupant-only sweep missed exactly this.
    free_slot = next(s for s in range(3)
                     if sched._st.occupant[s] is None)
    eng.reserve_pages(free_slot, 1)
    sched.release()
    assert eng.pages.free == eng.num_pages  # every page back
    assert eng.pages.reserved == 0  # every reservation cancelled
    assert (eng.table_len == 0).all()
    assert (eng.reserved_for == 0).all()
    # Reusable: a fresh run on the same engine completes cleanly.
    done, _ = Scheduler(eng).run(
        [Request(id=9, prompt=prompts[0], max_new_tokens=2)]
    )
    assert done[9].status == "ok"


def test_handoff_reservation_accounting_byte_whole():
    """ISSUE 15 satellite: PagePool reservation accounting across a
    prefill->decode hand-off. In one global tick the SOURCE releases
    everything (mapped page refs AND its unconsumed admission
    reservation — ``preempt`` goes through ``release_slot``) while the
    DESTINATION re-reserves the request's remaining worst case; and an
    ABORTED mid-transfer request (preempted, never adopted) leaves both
    pools byte-whole through the hardened ``release()`` sweep — the
    PR 13 pin extended across two engines."""
    cfg = ServeConfig(spec=TINY_SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=8)
    src_eng, dst_eng = InferenceEngine(cfg), InferenceEngine(cfg)
    src, dst = Scheduler(src_eng), Scheduler(dst_eng)
    prompt = np.arange(1, 7, dtype=np.int32)
    req = Request(id=0, prompt=prompt, max_new_tokens=10)
    need = src_eng.pages_needed(6 + 10)
    src.begin()
    dst.begin()
    src.submit(req)
    src.tick()  # admit + prefill + first token: active, pages held
    held = int(src_eng.table_len[0])
    assert held >= 1
    # Mid-flight the source holds mapped pages plus the rest of its
    # admission promise.
    assert src_eng.pages.free == src_eng.num_pages - held
    assert src_eng.pages.reserved == need - held

    pre = src.preempt(0)
    # Source side released in full: refs AND reservations, same tick.
    assert src_eng.pages.free == src_eng.num_pages
    assert src_eng.pages.reserved == 0
    assert int(src_eng.reserved_for[0]) == 0

    slot = dst.adopt(pre)
    # Destination re-reserved the worst case and mapped the moved
    # pages out of that promise.
    assert int(dst_eng.table_len[slot]) == held
    assert dst_eng.pages.reserved == need - held
    assert dst_eng.pages.free == dst_eng.num_pages - held
    done_d = None
    while not dst.idle:
        dst.tick()
    done_d, _ = dst.collect()
    assert done_d[0].status == "ok" and len(done_d[0].tokens) == 10
    src.release()
    dst.release()
    for eng in (src_eng, dst_eng):
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0

    # Aborted mid-transfer: preempt again on a fresh run, then DROP the
    # preempted state instead of adopting — release() returns both
    # pools byte-whole (the dumped pages were host copies; nothing on
    # device is pinned by them).
    src.begin()
    dst.begin()
    src.submit(req)
    src.tick()
    pre = src.preempt(0)
    assert pre.pos.shape[0] >= 1  # the dump really carried pages
    src.release()
    dst.release()
    for eng in (src_eng, dst_eng):
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0
        assert (eng.table_len == 0).all()
        assert (eng.reserved_for == 0).all()
