"""The linear and block-sparse kinds of ``models.hybrid`` (a state a slot
without pages; K and V rows in the global page group with a selector's
means beside them) against their plain reference
(``perf/sala_reference.py``: the token-by-token recurrence and a mask a
query), at toy sizes on the CPU, seeded random weights, fp32.

The selector's sizes are shrunk with the lengths so that selection
binds: pages of 4 rows, 4 of a slot's up to 16 attended past 16 rows of
context. The served path (``HybridEngine`` + ``Scheduler``: whole and
chunked prefill, then decode) is compared with the reference's full
forward pass in LOGITS; each mechanism the block adds is pinned by a
test that fails when it is left out or swapped for its neighbour.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.models import hybrid
from ddl_tpu.obs.trace import Tracer
from ddl_tpu.ops import linear_attention, sparse_attention
from ddl_tpu.serve import (InferenceEngine, Request, Scheduler, ServeConfig,
                           engine_cls)
from ddl_tpu.serve.hybrid_engine import HANDOFF, HybridEngine
from perf import sala_reference as ref
from perf import sala_weights as sw
from perf.serve_sala_runner import spec_of

TOY = sw.SalaSizes(
    name="toy", vocab=64, d_model=32, num_heads=4, head_dim=8, kv_heads=2,
    d_ff=64, mixers=(sw.SPARSE, sw.LINEAR, sw.LINEAR, sw.SPARSE), eps=1e-6,
    rope_base=10_000.0, scale_emb=12.0, scale_depth=1.4, depth=32,
    dim_model_base=16, kernel_size=4, kernel_stride=2, block_size=4, topk=4,
    init_blocks=1, window_size=8, dense_len=16)
SPEC = spec_of(TOY)
TOL = 2e-5  # fp32 on the CPU, two independent forwards


@pytest.fixture(scope="module")
def weights():
    return sw.make_weights(13, TOY, "float32")


def reference_logits(weights, seq, sizes=TOY):
    """The reference's logits at every position of ``seq``."""
    tokens = np.zeros(-(-len(seq) // 8) * 8, np.int32)
    tokens[:len(seq)] = seq
    return np.asarray(ref.all_logits(weights, jnp.asarray(tokens),
                                     sizes=sizes))[:len(seq)]


def engine(weights, spec=SPEC, **kw_):
    cfg = dict(spec=spec, slots=3, capacity=64, page_size=4, num_pages=48)
    cfg.update(kw_)
    return engine_cls(spec)(ServeConfig(**cfg), params=weights)


def decode(eng, seqs: dict, request_id=7):
    """One decode tick of the slots in ``seqs`` (slot -> its sequence so
    far, the last token not yet cached): ``(next [S], logits [S, V])``."""
    slots = eng.config.slots
    last, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    active = np.zeros(slots, bool)
    for slot, seq in seqs.items():
        last[slot], lengths[slot], active[slot] = seq[-1], len(seq) - 1, True
    return eng.decode(last, lengths, np.full(slots, request_id, np.int32),
                      active, want_logits=True)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, SPEC.vocab, n).astype(
        np.int32)


# -- (1) each kind, and the served path, against the reference in logits ------


@pytest.mark.parametrize("mixer", [sw.LINEAR, sw.SPARSE])
def test_each_kind_alone_agrees_with_the_reference(mixer):
    """The uncached forward of two layers of one kind (the chunked scan
    from a zero state; the selector over the call's own rows, then the
    masked form) against the reference's recurrence / mask a query."""
    sizes = dataclasses.replace(TOY, mixers=(mixer,) * 2)
    w = sw.make_weights(5, sizes, "float32")
    seq = prompt_of(53, 1)
    got, _ = hybrid.apply_hybrid(w, jnp.asarray(seq)[None], spec_of(sizes))
    np.testing.assert_allclose(np.asarray(got[0]),
                               reference_logits(w, seq, sizes), atol=TOL)


@pytest.mark.parametrize("chunks", [(41,), (8, 8, 8, 8, 8, 1), (16, 16, 9),
                                    (1,)])
def test_prefill_then_decode_agrees_with_reference(weights, chunks):
    """A prompt prefilled whole or in chunks (the state carried from one
    to the next, the later ones past ``dense_len`` choosing their blocks
    from the slot's means), then decoded one token at a time to position
    55, across pages of 4 rows: every logit row against the reference's
    full forward. ``(1,)`` is the token-by-token recurrence from the
    first position on."""
    eng = engine(weights)
    assert isinstance(eng, HybridEngine) and isinstance(eng, InferenceEngine)
    seq = prompt_of(56)
    want = reference_logits(weights, seq)
    base = 0
    for n in chunks:
        _, logits = eng.prefill(seq[base:base + n], slot=1, request_id=7,
                                base=base, want_logits=True)
        base += n
        np.testing.assert_allclose(logits[0], want[base - 1], atol=TOL)
        assert eng.last_counters == {
            "chunk": 0, "sparse": int(base > TOY.dense_len)}
    for at in range(base, 56):
        nxt, logits = decode(eng, {1: seq[:at + 1]})
        np.testing.assert_allclose(logits[1], want[at], atol=TOL)
        assert nxt[1] == int(np.argmax(logits[1]))
        held = at // 4 + 1
        assert eng.last_counters == {
            "state_slots": 1, "kv_pages": 2 * held,
            "sparse_pages": 2 * (held if at < TOY.dense_len else TOY.topk)}


def test_engine_uncached_forward_recurrence_and_reference_agree(weights):
    """Four ways to the same logits: the reference, the uncached forward
    (chunked scan, selector over its own rows), the engine's chunked
    prefill, and the engine fed one token at a time (the recurrence as
    written, the selector's means built a row at a time)."""
    seq = prompt_of(48, 3)
    want = reference_logits(weights, seq)
    plain, _ = hybrid.apply_hybrid(weights, jnp.asarray(seq)[None], SPEC)
    np.testing.assert_allclose(np.asarray(plain[0]), want, atol=TOL)
    eng = engine(weights)
    for base in range(0, 48, 16):
        _, logits = eng.prefill(seq[base:base + 16], slot=0, request_id=1,
                                base=base, want_logits=True)
        np.testing.assert_allclose(logits[0], want[base + 15], atol=TOL)
    eng.prefill(seq[:1], slot=2, request_id=2)
    for at in range(1, 48):
        _, logits = decode(eng, {2: seq[:at + 1]})
        np.testing.assert_allclose(logits[2], want[at], atol=TOL)


@pytest.mark.parametrize("prefill_chunk", [0, 8])
def test_scheduler_serves_the_block(weights, prefill_chunk):
    """``begin / submit / tick / collect`` over more requests than slots,
    prompts on both sides of ``dense_len``, chunks of one request between
    the others' decode ticks: every served token lies within ``TOL`` of
    the reference's best logit at its position (so chunked equals whole:
    both equal the reference), and the spans carry the counters."""
    eng = engine(weights, prefill_chunk=prefill_chunk)
    tracer = Tracer()
    sched = Scheduler(eng, eos_id=None, tracer=tracer)
    rng = np.random.default_rng(3)
    reqs = [Request(id=i, prompt=rng.integers(0, SPEC.vocab, n).astype(
        np.int32), max_new_tokens=m)
        for i, (n, m) in enumerate([(19, 9), (3, 14), (33, 6), (9, 12),
                                    (26, 5), (44, 8)])]
    sched.warmup(reqs[:2])
    sched.begin()
    for r in reqs:
        sched.submit(r)
    while not sched.idle:
        sched.tick()
    done, _ = sched.collect()
    sched.release()
    for r in reqs:
        out = done[r.id]
        assert out.status == "ok" and len(out.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, out.tokens[:-1]]).astype(np.int32)
        logits = reference_logits(weights, seq)[len(r.prompt) - 1:]
        gaps = logits.max(-1) - logits[np.arange(len(out.tokens)),
                                       np.asarray(out.tokens)]
        assert gaps.max() <= TOL, (r.id, gaps)
    assert eng.pages.free == eng.num_pages and eng.pages.reserved == 0
    spans = {n: [r["attrs"] for r in tracer.records if r["name"] == n]
             for n in ("serve.prefill", "serve.decode")}
    assert all(set(a) >= {"pages", "kv_pages", "sparse_pages", "state_slots"}
               and "moe_assigned" not in a for a in spans["serve.decode"])
    assert any(a["sparse_pages"] < a["kv_pages"]
               for a in spans["serve.decode"])
    chunks = [a["chunk"] for a in spans["serve.prefill"]]
    assert max(chunks) == (5 if prefill_chunk else 0)   # 44 tokens: 6 chunks
    assert {a["sparse"] for a in spans["serve.prefill"]} == {0, 1}


def test_cli_serves_the_named_kinds(capsys, monkeypatch):
    from ddl_tpu.cli import main

    monkeypatch.setitem(hybrid.NAMED_SPECS, "toy-two-mixers", SPEC)
    rc = main(["serve", "--platform", "cpu", "--model-spec", "toy-two-mixers",
               "--slots", "2", "--capacity", "64", "--page-size", "4",
               "--prefill-chunk", "8", "--num-prompts", "3", "--prompt-min",
               "4", "--prompt-max", "30", "--max-new-tokens", "6", "--json"])
    assert rc == 0
    assert '"variant": "serve"' in capsys.readouterr().out


def test_named_spec_is_the_configuration():
    sizes = sw.load_sizes("minicpm-sala-l8")
    spec = hybrid.NAMED_SPECS["minicpm-sala-l8"]
    assert spec == spec_of(sizes) and engine_cls(spec) is HybridEngine
    assert spec.layer_kinds == (hybrid.SPARSE,) + (hybrid.LINEAR,) * 6 \
        + (hybrid.SPARSE,)
    assert spec.residual_scale == 1.4 / 32 ** 0.5 and spec.logit_scale == 1 / 16
    assert spec.selector == (64, 16, 64, 1, 32, 8192)


# -- (2) the linear kind: chunked scan, state group ---------------------------


def _recurrence(state, q, k, v, rates):
    """Token by token through ``linear_attention.step``."""
    outs = []
    for t in range(q.shape[0]):
        o, state = linear_attention.step(
            state[None], q[None, t], k[None, t], v[None, t], rates,
            jnp.ones(1, bool))
        state = state[0]
        outs.append(o[0])
    return jnp.stack(outs), state


@pytest.mark.parametrize("t,length,chunk", [(32, 32, 8), (32, 21, 8),
                                            (24, 24, 256), (64, 3, 16)])
def test_chunked_scan_equals_the_token_by_token_recurrence(t, length, chunk):
    """From a state that is not zero, with padding behind the real rows:
    the real rows' outputs and the state after the last of them."""
    h, d = 4, 8
    keys = jax.random.split(jax.random.PRNGKey(t + length), 4)
    q, k, v = (jax.random.normal(keys[i], (t, h, d)) for i in range(3))
    start = jax.random.normal(keys[3], (h, d, d))
    rates = linear_attention.decay_rates(h)
    np.testing.assert_allclose(
        rates, [2.0 ** (-8 * (n + 1) / h) for n in range(h)], rtol=1e-6)
    got, end = linear_attention.scan_chunks(start, q, k, v, rates,
                                            jnp.int32(length), chunk)
    want, want_end = _recurrence(start, q[:length], k[:length], v[:length],
                                 rates)
    np.testing.assert_allclose(got[:length], want, atol=2e-5)
    np.testing.assert_allclose(end, want_end, atol=2e-5)


def test_the_fastest_head_does_not_overflow_a_chunk():
    """At the published 32 heads a chunk of 256 rows: ``lambda ** -255``
    of head 0 is past fp32, so the decay is built from differences."""
    h, d, t = 32, 4, 512
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(keys[i], (t, h, d)) for i in range(3))
    out, state = linear_attention.scan_chunks(
        jnp.zeros((h, d, d)), q, k, v, linear_attention.decay_rates(h),
        jnp.int32(t))
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(state)).all()


def test_a_readmitted_slot_starts_from_zero_state(weights):
    """A slot that served one request and is handed the next: the prefill
    program of the new request's first block zeroes the state itself, and
    a slot between two chunks of its prompt keeps its state through the
    others' decode ticks."""
    eng = engine(weights)
    first, second = prompt_of(30, 4), prompt_of(22, 5)
    eng.prefill(first, slot=1, request_id=1)
    decode(eng, {1: list(first) + [3]})
    state = np.asarray(eng.cache.extra[1])
    assert np.abs(state[1]).max() > 0 and not state[[0, 2]].any()
    eng.release_slot(1)
    eng.prefill(second[:16], slot=1, request_id=2)        # first chunk
    between = np.asarray(eng.cache.extra[1][1])
    eng.prefill(prompt_of(9, 6), slot=0, request_id=3)
    decode(eng, {0: list(prompt_of(9, 6)) + [1]})         # slot 1 inactive
    np.testing.assert_array_equal(np.asarray(eng.cache.extra[1][1]), between)
    _, logits = eng.prefill(second[16:], slot=1, request_id=2, base=16,
                            want_logits=True)
    np.testing.assert_allclose(logits[0], reference_logits(weights, second)[-1],
                               atol=TOL)
    assert eng.cache.extra[1].dtype == jnp.float32
    assert eng.cache.extra[1].shape == (3, 4, 8, 8)
    assert eng.cache.k[1] is None and eng.cache.v[1] is None


# -- (3) the sparse kind: selector, its cache, both sides of dense_len --------


def test_selected_blocks_equal_the_references_and_selection_binds(weights):
    """The blocks the program's selector picks for every query of every
    sparse layer are the reference's (an independent max-pool and sort);
    past ``dense_len`` they are ``topk`` of more, and attending every row
    instead gives other logits."""
    seq = prompt_of(56, 7)
    want = np.asarray(ref.attended_blocks(weights, jnp.asarray(seq),
                                          sizes=TOY))    # [2, Hkv, T, blocks]
    picked = []
    positions = jnp.arange(56)[None]
    sel, rates = SPEC.selector, linear_attention.decay_rates(4)

    def mix(layer, q, k, v):
        """``apply_hybrid``'s view, noting what the selector picks."""
        if SPEC.layer_kinds[layer] == hybrid.LINEAR:
            return linear_attention.scan_chunks(
                jnp.zeros((4, 8, 8)), q[0], k[0], v[0], rates, 56)[0][None]
        chosen = sparse_attention.select_blocks(
            q, sparse_attention.group_means(k, sel.stride), positions, sel,
            SPEC.head_dim ** -0.5)
        picked.append(np.asarray(sparse_attention.allowed_blocks(
            chosen, sparse_attention.attends_all(positions, sel)))[0])
        return hybrid.sparse_over_own_rows(q, k, v, positions, SPEC)

    hybrid.apply_layers(weights, jnp.asarray(seq)[None], SPEC, positions,
                        jnp.ones((1, 56), bool), mix)
    assert len(picked) == 2
    for layer in range(2):
        for t in range(56):
            visible = t // 4 + 1
            got = picked[layer][t][:, :visible]               # [Hkv, b]
            np.testing.assert_array_equal(got, want[layer, :, t, :visible])
            count = visible if t < TOY.dense_len else TOY.topk
            assert (want[layer, :, t].sum(-1) == count).all()
    # the two K/V heads choose for themselves somewhere
    assert (want[:, 0] != want[:, 1]).any()
    dense = dataclasses.replace(TOY, dense_len=64, topk=16)
    got = reference_logits(weights, seq, dense)
    assert np.abs(got[:16] - reference_logits(weights, seq)[:16]).max() <= TOL
    assert np.abs(got[40:] - reference_logits(weights, seq)[40:]).max() > 1e-3


def test_one_decode_batch_holds_slots_on_both_sides_of_dense_len(weights):
    """Three slots in one tick, at 10, 16 (the last context every row of
    which is attended) and 46 rows of context: each row of logits against
    the reference."""
    eng = engine(weights)
    seqs = {0: prompt_of(10, 8), 1: prompt_of(16, 9), 2: prompt_of(46, 10)}
    for slot, seq in seqs.items():
        eng.prefill(seq[:-1], slot=slot, request_id=slot)
    _, logits = decode(eng, seqs)
    for slot, seq in seqs.items():
        np.testing.assert_allclose(
            logits[slot], reference_logits(weights, seq)[-1], atol=TOL)
    assert eng.last_counters == {
        "state_slots": 3, "kv_pages": 2 * (3 + 4 + 12),
        "sparse_pages": 2 * (3 + 4 + 4)}


def test_selector_cache_holds_group_means_beside_the_rows(weights):
    """One pool keeps the head before the row, a head's K rows of a page
    and then its V rows; the selector's cache holds, through the same
    table, the mean of every whole group of K rows, written by the
    prefill that wrote them and completed a row at a time by decode."""
    eng = engine(weights)
    seq = prompt_of(23, 11)
    eng.prefill(seq[:21], slot=2, request_id=1)
    decode(eng, {2: seq[:22]})
    decode(eng, {2: seq[:23]})
    pk, means = np.asarray(eng.cache.k[0]), np.asarray(eng.cache.extra[0])
    assert pk.shape == (48, 2, 2 * 4, 8) and means.shape == (48 * 2 * 2, 8)
    assert eng.cache.v[0] is None
    assert np.abs(pk[eng.tables[2][0], :, 4:]).max() > 0     # the V rows
    table = eng.tables[2]
    for group in range(23 // 2):          # rows 2 g, 2 g + 1; 22 is alone
        page, g = table[group // 2], group % 2
        for head in range(2):
            rows = pk[page, head, 2 * g:2 * g + 2]
            np.testing.assert_allclose(means[(page * 2 + head) * 2 + g],
                                       rows.mean(0), atol=1e-6)


@pytest.mark.parametrize("what,kw_", [
    ("page_size", dict(page_size=8, num_pages=24)),
    ("chunk", dict(prefill_chunk=8)),
])
def test_the_pool_page_is_the_selectors_block(weights, what, kw_):
    spec = SPEC if what == "page_size" else dataclasses.replace(
        SPEC, sparse_stride=16, sparse_block=16, sparse_dense_len=64)
    if what == "chunk":
        kw_ = dict(kw_, page_size=16, num_pages=12)
    with pytest.raises(ValueError, match="sparse layer's block"):
        engine(weights, spec=spec, **kw_)


@pytest.mark.parametrize("field", ["sparse_stride", "sparse_local",
                                   "sparse_dense_len"])
def test_spec_refuses_a_selector_that_cannot_choose(field):
    bad = {"sparse_stride": 3, "sparse_local": 4, "sparse_dense_len": 12}
    with pytest.raises(ValueError, match="dense_len at least"):
        dataclasses.replace(SPEC, **{field: bad[field]})


# -- (4) the decode kernel, interpret mode, against the gathered path ---------

KP, KS, KD, KH, KG, KB, KTP = 40, 16, 128, 2, 8, 4, 12


def _kernel_case(name):
    """``(table [KB, KTP], blocks [KB, KH, W], q_pos [KB])``; the last
    slot is always free."""
    table = np.full((KB, KTP), -1, np.int32)
    q_pos = np.full(KB, -1, np.int32)
    rng = np.random.default_rng(len(name))
    pages = iter(rng.permutation(KP))

    def fill(slot, length):
        n = -(-length // KS)
        table[slot, :n] = [next(pages) for _ in range(n)]
        q_pos[slot] = length - 1

    width = 8
    blocks = np.full((KB, KH, width), -1, np.int32)
    if name == "every_page_of_short_slots":
        for slot, length in ((0, 3 * KS + 5), (1, 1), (2, 8 * KS)):
            fill(slot, length)
            n = -(-length // KS)
            blocks[slot, :, :n] = np.arange(n)
    elif name == "chosen_pages_of_long_slots":
        fill(0, 11 * KS + 3)
        fill(1, 12 * KS)
        blocks[0, 0] = [0, 11, 10, 4, 7, 2, 9, 8]     # best first, any order
        blocks[0, 1] = [0, 11, 10, 1, 3, 5, 6, 9]     # a head's own choice
        blocks[1, 0] = [0, 11, 10, 9, 2, 3, 4, 5]
        blocks[1, 1] = [0, 11, 10, 9, 8, 7, 6, 1]
    elif name == "both_kinds_and_an_idle_slot":
        fill(0, 10 * KS + 1)
        fill(1, 2 * KS)
        table[2, :3] = [next(pages) for _ in range(3)]   # mid-prefill
        blocks[0, :] = [0, 10, 9, 8, 3, 5, -1, -1]       # fewer than the list
        blocks[1, :, :2] = [0, 1]
    else:
        raise KeyError(name)
    return table, blocks, q_pos


@pytest.mark.parametrize("pages_per_step", [4, 3])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", ["every_page_of_short_slots",
                                  "chosen_pages_of_long_slots",
                                  "both_kinds_and_an_idle_slot"])
def test_kernel_matches_the_gathered_path(case, dtype, tol, pages_per_step):
    """``sparse_decode_attention`` (interpret mode) over listed pages read
    in place against ``gather_heads`` + ``attend_blocks`` with those
    blocks allowed; a slot with nothing listed gets zeros."""
    table, blocks, q_pos = _kernel_case(case)
    keys = jax.random.split(jax.random.PRNGKey(34), 2)
    pool = jax.random.normal(keys[0], (KP, KH, 2 * KS, KD), dtype)
    q = jax.random.normal(keys[1], (KB, KH, KG, KD), dtype)
    got = sparse_attention.sparse_decode_attention(
        q, pool, jnp.asarray(table), jnp.asarray(blocks),
        jnp.asarray(q_pos), scale=0.09, pages_per_step=pages_per_step,
        interpret=True)
    assert got.dtype == q.dtype and got.shape == (KB, KH, KG, KD)
    f32 = lambda a: a.astype(jnp.float32)
    allowed = (blocks[..., None] == np.arange(KTP)).any(-2)    # [B, H, TP]
    k_view, v_view = sparse_attention.gather_heads(f32(pool),
                                                   jnp.asarray(table))
    want = sparse_attention.attend_blocks(
        f32(q).reshape(KB, 1, KH * KG, KD), k_view, v_view,
        jnp.asarray(q_pos)[:, None], jnp.asarray(allowed)[:, None], KS, 0.09)
    want = np.asarray(want).reshape(KB, KH, KG, KD)
    got = np.asarray(got, np.float32)
    live = q_pos >= 0
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=0)
    assert (got[~live] == 0).all()


@pytest.mark.parametrize("group,head_dim,page,fits", [
    (16, 128, 64, True), (8, 256, 16, True),
    (2, 128, 64, False),     # a K/V head's query heads under a sublane tile
    (16, 64, 64, False),     # a head under a lane tile
    (16, 128, 8, False),     # a page under bf16's 16-row tile
])
def test_kernel_accepts_whole_tiles_only(group, head_dim, page, fits):
    assert sparse_attention.kernel_accepts(group, head_dim, page) is fits
    # the dense kernel's rule refuses what this pool is for: 2 K/V heads
    from ddl_tpu.ops.paged_attention import kernel_accepts

    assert not kernel_accepts(2, 128, 64)


def _sparse_decode(platform, monkeypatch=None):
    """One decode tick of the paged forward over sparse layers whose
    widths the kernel takes (16 query heads of 128 over 2 K/V heads,
    pages of 16), past ``dense_len`` (2 pages) for slot 0 alone."""
    spec = hybrid.HybridSpec(
        vocab=32, d_model=32, num_heads=16, head_dim=128, v_head_dim=128,
        kv_heads_global=2, d_ff=32, layer_kinds=(hybrid.SPARSE,) * 2,
        ffn_kinds=(hybrid.DENSE,) * 2, sparse_block=16, sparse_stride=4,
        sparse_topk=2, sparse_init=1, sparse_local=1, sparse_dense_len=32)
    from ddl_tpu.serve.cache import hybrid_cache

    params = hybrid.init_hybrid_params(jax.random.PRNGKey(3), spec)
    cache = hybrid_cache(spec, 12, 0, 16, jnp.float32)
    rand = lambda i, a: jax.random.normal(jax.random.PRNGKey(i), a.shape)
    pools = {i: (rand(i, cache.k[i]), None, rand(13 + i, cache.extra[i]))
             for i in range(2)}
    table = jnp.asarray([[3, 5, 7, 2], [1, -1, -1, -1], [9, 11, -1, -1]])
    active = jnp.asarray([True, True, False])
    positions = jnp.where(active, jnp.asarray([57, 9, 20]), -1)
    if monkeypatch is not None:
        monkeypatch.setattr(
            sparse_attention, "sparse_decode_attention", functools.partial(
                sparse_attention.sparse_decode_attention, interpret=True))

    def forward(params, pools, tokens):
        return hybrid.apply_hybrid_paged(
            params, pools, tokens, spec, page_size=16, g_table=table,
            w_table=None, positions=positions[:, None],
            real=active[:, None], last=positions, platform=platform)

    return forward, (params, pools, jnp.asarray([[1], [2], [3]]))


def test_decode_reads_the_listed_pages_in_place_on_a_tpu(monkeypatch):
    """The rule ``PagedMixer`` chooses by: on a TPU, at widths the kernel
    takes, a sparse layer's decode is one ``sparse_decode_attention`` a
    layer (one traced kernel) and no gathered view; the CPU gathers. Both
    give the same pools and hidden state."""
    forward, args = _sparse_decode("tpu", monkeypatch)
    trace = str(jax.make_jaxpr(forward)(*args))
    assert len(re.findall(r"jit\[\s*name=sparse_decode_attention",
                          trace)) == 2
    assert trace.count("pallas_call[") == 1
    assert "f32[3,2,64,128]" not in trace        # the gathered view
    plain, _ = _sparse_decode("cpu")
    assert "f32[3,2,64,128]" in str(jax.make_jaxpr(plain)(*args))
    assert "sparse_decode_attention" not in str(jax.make_jaxpr(plain)(*args))
    h, pools, _ = forward(*args)
    want_h, want_pools, _ = plain(*args)
    live = np.asarray([0, 1])
    np.testing.assert_allclose(np.asarray(h)[live], np.asarray(want_h)[live],
                               atol=2e-5)
    for at in (0, 2):   # the rows, the means
        np.testing.assert_array_equal(pools[0][at], want_pools[0][at])
        np.testing.assert_allclose(pools[1][at], want_pools[1][at], atol=2e-5)


# -- (5) what the kinds refuse, and what a swapped mechanism does -------------


@pytest.mark.parametrize("feature,kw_", [
    ("prefix cache", dict(prefix_slots=2)),
    ("speculation", dict(speculate_k=2)),
    ("int8 pool", dict(kv_dtype="int8")),
    ("contiguous cache", dict(page_size=0, num_pages=None)),
])
def test_unsupported_features_are_refused_by_name(weights, feature, kw_):
    with pytest.raises(ValueError, match=f"does not support the {feature}"):
        engine(weights, **kw_)


def test_handoff_names_the_state_and_the_selector_rows(weights):
    eng = engine(weights)
    assert not eng.handoff
    assert "selector rows" in HANDOFF and "recurrent state" in HANDOFF
    for call in (lambda: eng.dump_slot_pages(0),
                 lambda: eng.load_slot_pages(0, None),
                 lambda: eng.alias_slot_pages(1, 0, 4)):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()
    with pytest.raises(ValueError, match="handed off"):
        Scheduler(eng, role="prefill")


@pytest.mark.parametrize("swap", ["embed_scale", "residual_scale",
                                  "logit_scale", "decay", "qk_norm", "gate",
                                  "out_norm", "rope", "topk", "local"])
def test_a_swapped_mechanism_disagrees_with_the_reference(weights, swap,
                                                          monkeypatch):
    """Each thing the block adds, left out or swapped for its neighbour
    in the PROGRAM's spec or weights: the logits move by far more than the
    tolerance the tests hold."""
    spec, w = SPEC, weights
    blocks = lambda f: dict(w, blocks=[f(i, dict(b)) for i, b in
                                       enumerate(w["blocks"])])
    if swap == "embed_scale":
        spec = dataclasses.replace(spec, embed_scale=1.0)
    elif swap == "residual_scale":
        spec = dataclasses.replace(spec, residual_scale=1.4 / 8 ** 0.5)
    elif swap == "logit_scale":
        spec = dataclasses.replace(spec, logit_scale=1.0)
    elif swap == "decay":      # the slopes of another head count
        rates = linear_attention.decay_rates
        monkeypatch.setattr(linear_attention, "decay_rates",
                            lambda h: rates(2 * h)[:h])
    elif swap == "qk_norm":    # a gain that is not the reference's one
        w = blocks(lambda i, b: dict(b, qn=2.0 * b["qn"]))
    elif swap == "gate":
        w = blocks(lambda i, b: dict(b, wgate=0.0 * b["wgate"]))
    elif swap == "out_norm":
        w = blocks(lambda i, b: dict(b, on=3.0 * b["on"]) if "on" in b else b)
    elif swap == "rope":
        spec = dataclasses.replace(spec, rope_base_global=100.0)
    elif swap == "topk":
        spec = dataclasses.replace(spec, sparse_topk=3)
    elif swap == "local":
        spec = dataclasses.replace(spec, sparse_local=1)
    seq = prompt_of(40, 12)
    got, _ = hybrid.apply_hybrid(w, jnp.asarray(seq)[None], spec)
    want = reference_logits(weights, seq)
    assert np.abs(np.asarray(got[0]) - want).max() > 50 * TOL
