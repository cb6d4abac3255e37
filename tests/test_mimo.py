"""The window/global routed-expert family (``models.hybrid``) against its
plain reference (``perf/mimo_reference.py``), at toy sizes on the CPU,
seeded random weights, fp32.

The served path (``InferenceEngine`` + ``Scheduler`` over two page
groups) is compared with the reference's full forward pass in LOGITS;
the mechanisms the family adds are each pinned by a test that fails
when the mechanism is left out or swapped for its neighbour.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.models import hybrid
from ddl_tpu.obs.trace import Tracer
from ddl_tpu.ops import kv_cache, moe
from ddl_tpu.serve import (InferenceEngine, Request, Scheduler, ServeConfig,
                           engine_cls)
from perf import mimo_reference as ref
from perf import mimo_weights as mw

SPEC = hybrid.HybridSpec()
TOL = 2e-5  # fp32 on the CPU, two independent forwards


def sizes_of(spec: hybrid.HybridSpec) -> mw.MimoSizes:
    """The reference's sizes for a program spec."""
    return mw.MimoSizes(
        name="toy", vocab=spec.vocab, d_model=spec.d_model,
        num_heads=spec.num_heads, head_dim=spec.head_dim,
        v_head_dim=spec.v_head_dim,
        kv_heads=(spec.kv_heads_global, spec.kv_heads_window),
        rope_base=(spec.rope_base_global, spec.rope_base_window),
        rotary_dim=spec.rotary_dim, window=spec.window,
        value_scale=spec.value_scale, d_ff=spec.d_ff,
        expert_ff=spec.expert_ff, router_width=spec.num_experts,
        experts_held=spec.experts_held, top_k=spec.experts_per_token,
        layer_kinds=spec.layer_kinds, ffn_kinds=spec.ffn_kinds,
        eps=spec.norm_eps)


SIZES = sizes_of(SPEC)


@pytest.fixture(scope="module")
def weights():
    return mw.make_weights(11, SIZES, "float32")


def reference_logits(weights, seq, sizes=SIZES):
    """The reference's logits at every position of ``seq``."""
    pad = -(-len(seq) // 8) * 8
    tokens = np.zeros(pad, np.int32)
    tokens[:len(seq)] = seq
    return np.asarray(ref.all_logits(weights, jnp.asarray(tokens),
                                     sizes=sizes))[:len(seq)]


def engine(weights, spec=SPEC, **kw):
    cfg = dict(spec=spec, slots=3, capacity=64, page_size=4, num_pages=40)
    cfg.update(kw)
    return engine_cls(spec)(ServeConfig(**cfg), params=weights)


# -- (1) the served path against the reference, in logits ---------------------


@pytest.mark.parametrize("chunks", [(21,), (8, 8, 5), (16, 5)])
def test_prefill_then_decode_agrees_with_reference(weights, chunks):
    """A prompt longer than two windows (8), prefilled whole or in
    chunks, then 20 tokens decoded one by one: the generation crosses
    pages of 4 rows and slides the window past five of them."""
    eng = engine(weights)
    assert isinstance(eng, InferenceEngine) and eng.ring == 3
    assert eng.num_window_pages == 3 * eng.ring  # one ring a slot
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, SPEC.vocab, sum(chunks)).astype(np.int32)
    base = 0
    for n in chunks:
        tok, logits = eng.prefill(prompt[base:base + n], slot=1,
                                  request_id=7, base=base, want_logits=True)
        base += n
        want = reference_logits(weights, prompt[:base])[-1]
        assert logits.shape == (1, SPEC.vocab)
        np.testing.assert_allclose(logits[0], want, atol=TOL)
    seq = list(prompt) + [tok]
    slots = eng.config.slots
    for _ in range(20):
        last, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        active = np.zeros(slots, bool)
        last[1], lengths[1], active[1] = seq[-1], len(seq) - 1, True
        nxt, logits = eng.decode(last, lengths, np.full(slots, 7, np.int32),
                                 active, want_logits=True)
        want = reference_logits(weights, seq)[-1]
        np.testing.assert_allclose(logits[1], want, atol=TOL)
        assert int(nxt[1]) == int(np.argmax(logits[1]))
        seq.append(int(nxt[1]))
    assert eng.last_counters["moe_assigned"] == 3 * SPEC.experts_per_token
    assert set(eng.last_counters) == {"moe_assigned", "moe_touched",
                                      "win_pages"}


@pytest.mark.parametrize("prefill_chunk", [0, 8])
def test_scheduler_serves_the_family(weights, prefill_chunk):
    """``begin / submit / tick / collect`` over more requests than
    slots: every served token lies within ``TOL`` of the reference's
    best logit at its position, and the spans carry the counters."""
    eng = engine(weights, prefill_chunk=prefill_chunk)
    tracer = Tracer()
    sched = Scheduler(eng, eos_id=None, tracer=tracer)
    rng = np.random.default_rng(3)
    reqs = [Request(id=i, prompt=rng.integers(0, SPEC.vocab, n).astype(
        np.int32), max_new_tokens=m)
        for i, (n, m) in enumerate([(19, 9), (3, 14), (33, 6), (9, 12),
                                    (26, 5)])]
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
    assert eng.pages.free == eng.num_pages
    assert eng.win_pages.free == eng.num_window_pages
    assert eng.pages.reserved == eng.win_pages.reserved == 0
    spans = {n: [r["attrs"] for r in tracer.records if r["name"] == n]
             for n in ("serve.decode", "serve.prefill")}
    assert all({"pages", "moe_assigned", "moe_touched", "win_pages"}
               <= set(a) for a in spans["serve.decode"])
    assert all("moe_assigned" in a and "moe_touched" not in a
               for a in spans["serve.prefill"])


def test_cli_serves_a_named_spec(capsys, monkeypatch):
    from ddl_tpu.cli import main

    monkeypatch.setitem(hybrid.NAMED_SPECS, "toy", SPEC)
    rc = main(["serve", "--platform", "cpu", "--model-spec", "toy",
               "--slots", "2", "--capacity", "64", "--page-size", "4",
               "--num-prompts", "3", "--prompt-min", "4", "--prompt-max",
               "12", "--max-new-tokens", "6", "--json"])
    assert rc == 0
    assert '"variant": "serve"' in capsys.readouterr().out


# -- (2) the shares add up ----------------------------------------------------


def test_expert_shares_add_up_to_the_uncut_layer(weights):
    """16 experts held 4 ways: the four ranks' routed parts, added, are
    the uncut reference's layer output."""
    blk = weights["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (24, SPEC.d_model))
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    want = ref.routed(x, blk, SIZES, mm)
    experts, w = moe.route(x, blk["wr"], blk["rc"], SPEC.experts_per_token)
    real = jnp.ones(24, bool)
    total, assigned = 0.0, 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        part, counts = moe.routed_ffn(
            x, blk["eg"][held], blk["eu"][held], blk["ed"][held], experts,
            w, real, first=first, tile=8)
        assert float(jnp.abs(part).max()) > 0
        total, assigned = total + part, assigned + int(counts[0])
    assert assigned == 24 * SPEC.experts_per_token  # none dropped, none twice
    np.testing.assert_allclose(total, want, atol=TOL)
    # and a rank's part is what the reference gives when handed that share
    share = dataclasses.replace(SIZES, experts_held=(4, 8))
    cut = dict(blk, eg=blk["eg"][4:8], eu=blk["eu"][4:8], ed=blk["ed"][4:8])
    part, _ = moe.routed_ffn(x, cut["eg"], cut["eu"], cut["ed"], experts, w,
                             real, first=4, tile=8)
    np.testing.assert_allclose(part, ref.routed(x, cut, share, mm), atol=TOL)


def test_padding_rows_are_not_routed(weights):
    blk = weights["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (16, SPEC.d_model))
    experts, w = moe.route(x, blk["wr"], blk["rc"], SPEC.experts_per_token)
    real = jnp.arange(16) < 5
    out, counts = moe.routed_ffn(x, blk["eg"], blk["eu"], blk["ed"], experts,
                                 w, real, first=0, tile=8)
    assert int(counts[0]) == 5 * SPEC.experts_per_token
    assert float(jnp.abs(out[5:]).max()) == 0.0


# -- (3) selection by sc + c, weights by sc -----------------------------------


def test_router_selects_by_corrected_score_and_weighs_by_score():
    n, k = 16, 4
    x = jnp.eye(8, dtype=jnp.float32)[:1]
    wr = jnp.zeros((8, n)).at[0].set(jnp.linspace(-2.0, 2.0, n))
    sc = np.asarray(jax.nn.sigmoid(wr[0]))
    c = jnp.zeros(n).at[jnp.arange(4)].set(5.0)  # lifts the four lowest
    experts, w = moe.route(x, wr, c, k)
    assert set(np.asarray(experts[0])) == {0, 1, 2, 3}       # by sc + c ...
    assert set(np.argsort(-sc)[:k]) == {12, 13, 14, 15}      # ... not by sc
    chosen = sc[np.asarray(experts[0])]
    np.testing.assert_allclose(w[0], chosen / chosen.sum(), rtol=1e-6)
    lifted = chosen + 5.0                                    # not by sc + c
    assert np.abs(np.asarray(w[0]) - lifted / lifted.sum()).max() > 0.05


# -- (4) the sink --------------------------------------------------------------


def _attend(sink, v=None):
    t, hq, hkv, d = 24, 4, 2, 12
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, t, hq, d))
    k = jax.random.normal(ks[1], (1, t, hkv, d))
    v = jax.random.normal(ks[2], (1, t, hkv, 8)) if v is None else v
    pos = jnp.arange(t)[None]
    return q, k, v, kv_cache.attend_grouped(q, k, v, pos, pos, window=8,
                                            sink=sink)


def test_sink_at_minus_infinity_is_plain_windowed_softmax():
    *_, plain = _attend(None)
    *_, sunk = _attend(jnp.full((4,), -jnp.inf))
    np.testing.assert_array_equal(plain, sunk)


def test_sink_takes_the_reference_share_of_each_row():
    sink = jnp.asarray([0.5, -1.0, 2.0, 0.0])
    ones = jnp.ones((1, 24, 2, 8))
    q, k, _, got = _attend(sink, ones)  # V of ones: the output is the row sum
    want = ref.attention(q[0], k[0], ones[0], jnp.arange(24), 8, sink,
                         "fp32")
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    assert float(got.max()) < 1.0
    # head 2's sink is the largest: its rows give up the most
    assert float(got[0, :, 2].mean()) < float(got[0, :, 1].mean())


# -- (5) each kind its own heads and rotary ------------------------------------


def test_kinds_have_their_own_kv_heads_and_pools(weights):
    eng = engine(weights)
    for i, kind in enumerate(SPEC.layer_kinds):
        hkv = (SPEC.kv_heads_window if kind == hybrid.WINDOW
               else SPEC.kv_heads_global)
        pages = eng.num_window_pages if kind == hybrid.WINDOW \
            else eng.num_pages
        assert weights["blocks"][i]["wk"].shape[1] == hkv * SPEC.head_dim
        assert weights["blocks"][i]["wv"].shape[1] == hkv * SPEC.v_head_dim
        if kind == hybrid.GLOBAL:  # one pool, the head before the row
            assert eng.cache.k[i].shape == (pages, hkv, 4, 128 + 128)
            assert eng.cache.v[i] is None
        else:
            assert eng.cache.k[i].shape == (pages, 4, hkv * SPEC.head_dim)
            assert eng.cache.v[i].shape == (pages, 4, hkv * SPEC.v_head_dim)
        assert ("sink" in weights["blocks"][i]) == (kind == hybrid.WINDOW)


def test_rotary_touches_the_first_third_only():
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 5, 2, 12))
    pos = jnp.arange(5)[None] + 3
    out = hybrid.partial_rope(x, pos, 10_000.0, 4)
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    assert float(jnp.abs(out[..., :4] - x[..., :4]).min()) > 0
    # pairs (i, i + 2): a rotation keeps each pair's norm
    np.testing.assert_allclose(out[..., 0] ** 2 + out[..., 2] ** 2,
                               x[..., 0] ** 2 + x[..., 2] ** 2, rtol=1e-5)


@pytest.mark.parametrize("swap", ["rope_base", "rotary_dim"])
def test_swapped_rotary_disagrees_with_the_reference(weights, swap):
    """The program with the two kinds' bases exchanged, or with rotary on
    the whole head, no longer matches: each kind reads its own base, and
    two thirds of a head pass through."""
    tokens = np.arange(24, dtype=np.int32)[None] % SPEC.vocab
    want = reference_logits(weights, tokens[0])
    good, _ = hybrid.apply_hybrid(weights, jnp.asarray(tokens), SPEC)
    np.testing.assert_allclose(good[0], want, atol=TOL)
    bad = dataclasses.replace(
        SPEC, rope_base_global=SPEC.rope_base_window,
        rope_base_window=SPEC.rope_base_global) if swap == "rope_base" \
        else dataclasses.replace(SPEC, rotary_dim=SPEC.head_dim)
    got, _ = hybrid.apply_hybrid(weights, jnp.asarray(tokens), bad)
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * TOL


# -- (6) the two page groups ----------------------------------------------------


def test_global_pool_keeps_a_heads_k_and_v_in_one_row(weights):
    """A global layer's ONE pool, the head before the row: after a prefill
    of 9 tokens into slot 1 the slot's pages hold, for each K/V head and
    row, ``[k 12 | zeros to 128 | v 8 | zeros to 256]``, what ``project``
    gives for that token; every other page is untouched; and the view
    gathered through the table is those rows again."""
    from ddl_tpu.ops.paged_attention import gather_grouped

    eng = engine(weights)
    prompt = (np.arange(9, dtype=np.int32) * 5 + 1) % SPEC.vocab
    eng.prefill(prompt, slot=1, request_id=3)
    layer = SPEC.layers_of(hybrid.GLOBAL)[0]
    assert layer == 0  # its input is the embedding: project it here
    x = hybrid.rms_norm(jnp.asarray(weights["embed"])[prompt][None],
                        weights["blocks"][0]["ln1"], SPEC.norm_eps)
    _, k, v = hybrid.project(x, weights["blocks"][0], SPEC, 0,
                             jnp.arange(9)[None])
    pool = np.asarray(eng.cache.k[layer])
    pages = eng.tables[1][:3]
    assert (pages >= 0).all() and (eng.tables[1][3:] < 0).all()
    rows = pool[pages].transpose(0, 2, 1, 3).reshape(12, 1, 256)[:9]
    np.testing.assert_allclose(rows[..., :12], np.asarray(k[0]), atol=1e-6)
    np.testing.assert_allclose(rows[..., 128:136], np.asarray(v[0]),
                               atol=1e-6)
    assert (rows[..., 12:128] == 0).all() and (rows[..., 136:] == 0).all()
    others = np.setdiff1d(np.arange(eng.num_pages), pages)
    assert (pool[others] == 0).all()
    kv, vv = gather_grouped(eng.cache.k[layer], jnp.asarray(eng.tables[1:2]),
                            SPEC.head_dim, SPEC.v_head_dim)
    np.testing.assert_array_equal(np.asarray(kv)[0, :9], rows[..., :12])
    np.testing.assert_array_equal(np.asarray(vv)[0, :9], rows[..., 128:136])


def test_reset_releases_the_old_pools_before_it_builds_the_new(
        weights, monkeypatch):
    """``Scheduler.warmup`` resets the engine twice; a reset that built
    the new pools while it held the old ones was where the cell's
    ``memory_peak_bytes`` was reached (PERF.md section 6, PR 35)."""
    from ddl_tpu.serve import hybrid_engine

    eng = engine(weights)
    seen, build = [], hybrid_engine.hybrid_cache
    monkeypatch.setattr(
        hybrid_engine, "hybrid_cache",
        lambda *a, **k: seen.append(eng.cache) or build(*a, **k))
    eng.reset()
    assert seen == [None] and eng.cache.k[0].shape[0] == eng.num_pages


def test_window_page_is_freed_when_its_last_row_leaves_the_window(weights):
    """Pages of 4 rows, a window of 8: page ``j`` (rows ``4j .. 4j + 3``)
    is held while the query at ``L`` still sees row ``4j + 3 > L - 8``,
    and is freed by the tick that writes row ``4j + 11``. The global
    group keeps every page."""
    eng = engine(weights)
    prompt = np.arange(6, dtype=np.int32)
    tok, _ = eng.prefill(prompt, slot=0, request_id=0)
    assert sorted(eng.win_logical[0]) == [-1, 0, 1]
    slots = eng.config.slots
    for at in range(6, 30):
        last, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        active = np.zeros(slots, bool)
        last[0], lengths[0], active[0] = tok, at, True
        nxt, _ = eng.decode(last, lengths, np.zeros(slots, np.int32), active)
        tok = int(nxt[0])
        held = {int(j) for j in eng.win_logical[0] if j >= 0}
        assert held == {j for j in range(at // 4 + 1) if 4 * j + 3 > at - 8}
        assert eng.last_counters["win_pages"] == len(held) <= eng.ring
        assert int(eng.table_len[0]) == at // 4 + 1  # global: all kept
        assert eng.pages.free == eng.num_pages - int(eng.table_len[0])
    eng.release_slot(0)
    assert eng.win_pages.free == eng.num_window_pages
    assert eng.pages.free == eng.num_pages


@pytest.mark.parametrize("short", ["global", "window"])
def test_admission_refuses_when_either_group_is_short(weights, short):
    """Admission counts both groups. The global group holds pages for
    one request only: of two slots' requests the second is admitted when
    the first has finished, never beside it. The window group holds one
    ring a slot: with every ring spoken for it refuses, however many
    global pages are free."""
    if short == "window":
        eng = engine(weights, slots=2)
        need = eng.pages_needed(12 + 8)
        eng.reserve_pages(0, need)
        assert eng.can_admit(need)
        eng.reserve_pages(1, need)
        assert eng.pages.available >= need and not eng.can_admit(need)
        eng.release_slot(1)
        assert eng.can_admit(need)
        return
    eng = engine(weights, slots=2, num_pages=6)
    need = eng.pages_needed(12 + 8)
    assert eng.can_admit(need)
    eng.reserve_pages(0, need)
    assert not eng.can_admit(need)
    eng.release_slot(0)
    assert eng.can_admit(need)
    tracer = Tracer()
    sched = Scheduler(eng, eos_id=None, tracer=tracer)
    reqs = [Request(id=i, prompt=np.full(12, i + 1, np.int32),
                    max_new_tokens=8) for i in range(2)]
    done, _ = sched.run(reqs)
    assert all(done[i].status == "ok" and len(done[i].tokens) == 8
               for i in range(2))
    admits = [r["attrs"]["step"] for r in tracer.records
              if r["name"] == "admit"]
    finished = [r["attrs"]["step"] for r in tracer.records
                if r["name"] == "complete"]
    assert admits[1] > admits[0] and admits[1] >= finished[0]


# -- (7) what the family does not serve yet -------------------------------------


@pytest.mark.parametrize("feature,kw", [
    ("prefix cache", dict(prefix_slots=2)),
    ("speculation", dict(speculate_k=2)),
    ("int8 pool", dict(kv_dtype="int8")),
    ("tensor parallelism", dict(tensor_parallel=2)),
    ("contiguous cache", dict(page_size=0, num_pages=0)),
])
def test_unsupported_features_are_refused_by_name(weights, feature, kw):
    with pytest.raises(ValueError, match=feature):
        engine(weights, **kw)


def test_handoff_is_refused_by_name(weights):
    eng = engine(weights)
    with pytest.raises(ValueError, match="handed off"):
        Scheduler(eng, role="prefill")
    for call in (lambda: eng.dump_slot_pages(0),
                 lambda: eng.load_slot_pages(0, None, None, None),
                 lambda: eng.alias_slot_pages(1, 0, 4)):
        with pytest.raises(NotImplementedError, match="hand-off"):
            call()


def test_named_spec_counts_the_published_cut():
    spec = hybrid.NAMED_SPECS["mimo-v2-flash-ep16"]
    assert spec.num_params == 3_429_955_392
    assert spec.layer_kinds.count(hybrid.WINDOW) == 5
    assert (spec.kv_heads_global, spec.kv_heads_window) == (4, 8)


def test_an_engine_class_refuses_the_other_familys_spec(weights):
    with pytest.raises(ValueError, match="engine_cls"):
        InferenceEngine(ServeConfig(spec=SPEC, slots=1, capacity=64,
                                    page_size=4))
    with pytest.raises(ValueError, match="engine_cls"):
        engine_cls(SPEC)(ServeConfig(slots=1, capacity=64, page_size=4))
    assert engine_cls(ServeConfig().spec) is InferenceEngine
