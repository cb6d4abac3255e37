"""The latent-attention kind of ``models.hybrid`` (a compressed row a
token in the global page group, routed experts beside a shared one)
against its plain reference (``perf/k2_reference.py``: the published,
up-projected form only), at toy sizes on the CPU, seeded random weights,
fp32.

The served path (``HybridEngine`` + ``Scheduler``: whole and chunked
prefill in the up-projected form, decode in the absorbed form) is
compared with the reference's full forward pass in LOGITS; each
mechanism the block adds is pinned by a test that fails when it is left
out or swapped for its neighbour.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.models import hybrid
from ddl_tpu.obs.trace import Tracer
from ddl_tpu.ops import kv_cache, moe
from ddl_tpu.serve import (InferenceEngine, Request, Scheduler, ServeConfig,
                           engine_cls)
from ddl_tpu.serve.hybrid_engine import HybridEngine
from perf import k2_reference as ref
from perf import k2_weights as kw
from perf.serve_k2_runner import spec_of

# Two latent layers, dense + routed; 16 experts of which ranks of 4 hold
# 4 each (this one: experts 4-7); a shared expert; YaRN with an original
# context of 64 so that its ramp (low 0, high 3) lies inside the toy's 8
# frequencies.
TOY = kw.K2Sizes(
    name="toy", vocab=64, d_model=32, num_heads=4, q_lora=24, kv_lora=16,
    nope_dim=8, rope_dim=16, v_head_dim=8, rope_base=10_000.0,
    rope_factor=4.0, rope_original=64, beta_fast=8.0, beta_slow=1.0,
    mscale=1.0, mscale_all_dim=1.0, d_ff=64, expert_ff=16, shared_ff=16,
    router_width=16, experts_held=(4, 8), top_k=4, route_scale=2.5,
    ffn_kinds=(0, 1), eps=1e-6)
SPEC = spec_of(TOY)
TOL = 2e-5  # fp32 on the CPU, two independent forwards


@pytest.fixture(scope="module")
def weights():
    return kw.make_weights(13, TOY, "float32")


def reference_logits(weights, seq, sizes=TOY):
    """The reference's logits at every position of ``seq``."""
    tokens = np.zeros(-(-len(seq) // 8) * 8, np.int32)
    tokens[:len(seq)] = seq
    return np.asarray(ref.all_logits(weights, jnp.asarray(tokens),
                                     sizes=sizes))[:len(seq)]


def engine(weights, spec=SPEC, **kw_):
    cfg = dict(spec=spec, slots=3, capacity=64, page_size=4, num_pages=40)
    cfg.update(kw_)
    return engine_cls(spec)(ServeConfig(**cfg), params=weights)


def decode_one(eng, slot, seq, request_id=7):
    """One decode tick of ``slot`` alone: ``(next, logits [vocab])``."""
    slots = eng.config.slots
    last, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    active = np.zeros(slots, bool)
    last[slot], lengths[slot], active[slot] = seq[-1], len(seq) - 1, True
    nxt, logits = eng.decode(last, lengths,
                             np.full(slots, request_id, np.int32), active,
                             want_logits=True)
    return int(nxt[slot]), logits[slot]


# -- (1) the served path against the reference, in logits ---------------------


@pytest.mark.parametrize("chunks", [(21,), (8, 8, 5), (16, 5)])
def test_prefill_then_decode_agrees_with_reference(weights, chunks):
    """A prompt prefilled whole (its own rows, up-projected) or in chunks
    (the later ones over the slot's table), then 20 tokens decoded one by
    one in the absorbed form, across pages of 4 rows."""
    eng = engine(weights)
    assert isinstance(eng, HybridEngine) and isinstance(eng, InferenceEngine)
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
        assert set(eng.last_counters) == {"moe_assigned"}
    seq = list(prompt) + [tok]
    for _ in range(20):
        nxt, logits = decode_one(eng, 1, seq)
        np.testing.assert_allclose(logits, reference_logits(weights, seq)[-1],
                                   atol=TOL)
        assert nxt == int(np.argmax(logits))
        assert eng.last_counters["latent_rows"] == len(seq)
        seq.append(nxt)
    assert set(eng.last_counters) == {"moe_assigned", "moe_touched",
                                      "latent_rows"}


@pytest.mark.parametrize("prefill_chunk", [0, 8])
def test_scheduler_serves_the_block(weights, prefill_chunk):
    """``begin / submit / tick / collect`` over more requests than slots:
    every served token lies within ``TOL`` of the reference's best logit
    at its position, and the spans carry the counters."""
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
    assert eng.pages.free == eng.num_pages and eng.pages.reserved == 0
    spans = {n: [r["attrs"] for r in tracer.records if r["name"] == n]
             for n in ("serve.decode", "serve.prefill")}
    assert spans["serve.decode"] and all(
        {"pages", "moe_assigned", "moe_touched", "latent_rows"} <= set(a)
        and "win_pages" not in a for a in spans["serve.decode"])
    assert all("moe_assigned" in a and "latent_rows" not in a
               for a in spans["serve.prefill"])


def test_cli_serves_the_named_kind(capsys, monkeypatch):
    from ddl_tpu.cli import main

    monkeypatch.setitem(hybrid.NAMED_SPECS, "toy-latent", SPEC)
    rc = main(["serve", "--platform", "cpu", "--model-spec", "toy-latent",
               "--slots", "2", "--capacity", "64", "--page-size", "4",
               "--num-prompts", "3", "--prompt-min", "4", "--prompt-max",
               "12", "--max-new-tokens", "6", "--json"])
    assert rc == 0
    assert '"variant": "serve"' in capsys.readouterr().out


# -- (2) one attention, two forms ---------------------------------------------


def test_absorbed_and_up_projected_attention_agree(weights):
    """One layer in fp32: folding ``W_kvb`` into the query and the output
    (here 4 query heads of 32 over ONE K/V row) is the published
    attention over up-projected heads of 24 / 8, to 1e-5, under a causal
    mask with unwritten rows."""
    blk = weights["blocks"][1]
    b, t, c = 2, 3, 24
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(ks[0], (b, t, SPEC.num_heads, 24))
    rows = jax.random.normal(ks[1], (b, c, SPEC.latent_row))
    q_pos = jnp.asarray([[9, 10, 11], [20, 21, 22]])
    k_pos = jnp.where(jnp.arange(c)[None] <= jnp.asarray([[11], [22]]),
                      jnp.arange(c)[None], -1)
    k, v = hybrid.latent_kv(rows, blk, SPEC)
    assert k.shape == (b, c, 4, 24) and v.shape == (b, c, 4, 8)
    want = kv_cache.attend_grouped(q, k, v, q_pos, k_pos,
                                   scale=SPEC.latent_scale)
    got = hybrid.latent_absorbed(
        q, blk, SPEC, SPEC.latent_row, lambda qa: kv_cache.attend_grouped(
            qa, rows[:, :, None], rows[:, :, None, :SPEC.kv_lora_rank],
            q_pos, k_pos, scale=SPEC.latent_scale))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.1


def test_attend_grouped_takes_the_scale():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 6, 4, 12))
    k = jax.random.normal(ks[1], (1, 6, 2, 12))
    v = jax.random.normal(ks[2], (1, 6, 2, 8))
    pos = jnp.arange(6)[None]
    plain = kv_cache.attend_grouped(q, k, v, pos, pos)
    same = kv_cache.attend_grouped(q, k, v, pos, pos, scale=12 ** -0.5)
    other = kv_cache.attend_grouped(q, k, v, pos, pos, scale=0.5)
    np.testing.assert_allclose(same, plain, atol=1e-6)
    assert float(jnp.abs(other - plain).max()) > 0.01


# -- (3) the shares add up ----------------------------------------------------


def test_expert_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        weights):
    """16 experts held 4 ways: the four ranks' routed parts, added, and
    the shared expert counted ONCE, are the uncut reference's FFN."""
    whole = dataclasses.replace(TOY, experts_held=(0, 16))
    full = kw.make_weights(13, whole, "float32")["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (24, SPEC.d_model))
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    shared = ref.gated(x, full["sg"], full["su"], full["sd"], mm)
    want = ref.routed(x, full, whole, mm) + shared
    experts, w = moe.route(x, full["wr"], full["rc"], SPEC.experts_per_token,
                           SPEC.route_scale)
    real = jnp.ones(24, bool)
    total, assigned = 0.0, 0
    for first in range(0, 16, 4):
        held = slice(first, first + 4)
        part, counts = moe.routed_ffn(
            x, full["eg"][held], full["eu"][held], full["ed"][held], experts,
            w, real, first=first, tile=8)
        assert float(jnp.abs(part).max()) > 0
        total, assigned = total + part, assigned + int(counts[0])
    assert assigned == 24 * SPEC.experts_per_token  # none dropped, none twice
    np.testing.assert_allclose(total + shared, want, atol=TOL)
    assert float(jnp.abs(shared).max()) > 100 * TOL
    # and the block's FFN on this rank is its own share plus the shared
    # expert, whole: what the reference gives when handed that share
    cut = dict(full, eg=full["eg"][4:8], eu=full["eu"][4:8],
               ed=full["ed"][4:8])
    h = jnp.zeros((1, 24, SPEC.d_model))
    blk = dict(cut, ln2=jnp.ones(SPEC.d_model))
    got = _ffn_of_block(h + x[None], blk)
    xn = ref.rms(x, 1.0, TOY.eps)
    np.testing.assert_allclose(
        got[0] - x, ref.routed(xn, cut, TOY, mm)
        + ref.gated(xn, cut["sg"], cut["su"], cut["sd"], mm), atol=TOL)


def _ffn_of_block(h, blk):
    """``apply_block``'s second half alone: a mixer that adds nothing."""
    blk = dict(blk, ln1=jnp.ones(SPEC.d_model),
               wo=jnp.zeros((SPEC.num_heads * SPEC.v_head_dim, SPEC.d_model)),
               **{n: jnp.zeros(s) for n, s in SPEC.block_shapes(1).items()
                  if n in ("wqa", "qn", "wqb", "wkva", "kvn", "wkvb")})
    b, t, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    mix = lambda layer, q, k, v: jnp.zeros((b, t, SPEC.num_heads,
                                            SPEC.v_head_dim))
    out, _ = hybrid.apply_block(h, blk, SPEC, 1, pos, jnp.ones((b, t), bool),
                                mix)
    return out


def test_route_scales_the_weights_as_the_reference_does(weights):
    blk = weights["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(8), (40, SPEC.d_model))
    chosen, want = ref.choose(x, blk, TOY)
    experts, w = moe.route(x, blk["wr"], blk["rc"], TOY.top_k,
                           TOY.route_scale)
    np.testing.assert_array_equal(experts, chosen)
    np.testing.assert_allclose(w, want, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), TOY.route_scale, rtol=1e-5)
    _, unit = moe.route(x, blk["wr"], blk["rc"], TOY.top_k)
    np.testing.assert_allclose(unit.sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(w, TOY.route_scale * unit, rtol=1e-5)


# -- (4) YaRN -------------------------------------------------------------------


@pytest.mark.parametrize("setting", [
    dict(rope_dim=16, base=10_000.0, original=64, fast=8.0, slow=1.0,
         factor=4.0, low=0, high=3),
    dict(rope_dim=64, base=50_000.0, original=4096, fast=32.0, slow=1.0,
         factor=32.0, low=8, high=20),
    dict(rope_dim=64, base=50_000.0, original=4096, fast=1.0, slow=1.0,
         factor=32.0, low=19, high=20),
])
def test_yarn_frequencies_follow_the_formula(setting):
    s = setting
    spec = dataclasses.replace(
        SPEC, rope_dim=s["rope_dim"], rope_base_global=s["base"],
        rope_original=s["original"], rope_beta_fast=s["fast"],
        rope_beta_slow=s["slow"], rope_factor=s["factor"])
    got = np.asarray(hybrid.yarn_freqs(spec))
    dim = s["rope_dim"]
    turn = lambda beta: dim * math.log(s["original"] / (2 * math.pi * beta)) \
        / (2 * math.log(s["base"]))
    low, high = math.floor(turn(s["fast"])), math.ceil(turn(s["slow"]))
    assert (low, high) == (s["low"], s["high"]) and low != high
    i = np.arange(dim // 2)
    theta = s["base"] ** (-2.0 * i / dim)
    r = np.clip((i - low) / (high - low), 0, 1)
    want = theta * (1 - r) + theta / s["factor"] * r
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got[:low + 1], theta[:low + 1], rtol=1e-5)
    np.testing.assert_allclose(got[high:], theta[high:] / s["factor"],
                               rtol=1e-5)
    sizes = dataclasses.replace(
        TOY, rope_dim=dim, rope_base=s["base"], rope_original=s["original"],
        beta_fast=s["fast"], beta_slow=s["slow"], rope_factor=s["factor"])
    theirs, bounds = ref.yarn_frequencies(sizes)
    np.testing.assert_allclose(got, theirs, rtol=1e-6)
    assert bounds == (low, high)


def test_the_published_scale():
    spec = hybrid.NAMED_SPECS["kimi-k2-ep32"]
    m = 0.1 * math.log(32) + 1
    assert abs(m - 1.346574) < 1e-6
    assert abs(spec.latent_scale - 192 ** -0.5 * m * m) < 1e-12
    assert abs(spec.latent_scale - 0.1308608) < 1e-6
    plain = dataclasses.replace(spec, rope_factor=1.0)
    assert plain.latent_scale == 192 ** -0.5


# -- (5) what is cached, and where ----------------------------------------------


def test_no_window_layer_builds_no_window_group(weights):
    """A pattern without a window layer: no window pool, no ring tables,
    and admission counts the global group alone."""
    eng = engine(weights, slots=2)
    assert eng.ring == 0 and eng.num_window_pages == 0
    assert not hasattr(eng, "win_pages") and not hasattr(eng, "win_tables")
    assert all(v is None for v in eng.cache.v)
    need = eng.pages_needed(12 + 8)
    eng.reserve_pages(0, need)
    eng.reserve_pages(1, need)
    assert eng.can_admit(need)  # 40 pages: only the global group counts
    eng.release_slot(0)
    eng.release_slot(1)
    assert eng.pages.reserved == 0
    # a spec of latent layers needs no window to be legal
    dataclasses.replace(SPEC, window=0)
    with pytest.raises(ValueError, match="window"):
        dataclasses.replace(SPEC, window=0, layer_kinds=(hybrid.LATENT,
                                                         hybrid.WINDOW))


def test_latent_pool_holds_one_compressed_row_a_token(weights):
    """``[c | k_r]``, 32 values here and 576 at the published widths, in
    a pool row of whole 128-lane tiles (128 here, 640 there), the rest
    zeros."""
    from ddl_tpu.serve.cache import latent_pool_width

    eng = engine(weights)
    for pool in eng.cache.k:
        assert pool.shape == (eng.num_pages, 4, 128)
    prompt = np.arange(6, dtype=np.int32)
    eng.prefill(prompt, slot=0, request_id=0)
    page = int(eng.tables[0, 0])
    x = weights["embed"][jnp.asarray(prompt)]
    blk = weights["blocks"][0]
    row = ref.rms(x, blk["ln1"], TOY.eps) @ blk["wkva"]
    c = ref.rms(row[:, :TOY.kv_lora], blk["kvn"], TOY.eps)
    k_r = ref.rotary(row[:, None, TOY.kv_lora:], jnp.arange(6), TOY)[:, 0]
    got = np.asarray(eng.cache.k[0][page])
    np.testing.assert_allclose(got[:4, :TOY.kv_lora], c[:4], atol=1e-5)
    np.testing.assert_allclose(got[:4, TOY.kv_lora:TOY.latent_row], k_r[:4],
                               atol=1e-5)
    assert not got[:, TOY.latent_row:].any()
    spec = hybrid.NAMED_SPECS["kimi-k2-ep32"]
    assert spec.latent_row == 576 and latent_pool_width(spec) == 640
    assert spec.num_params == 3_496_763_904
    assert spec.layers_of(hybrid.LATENT) == (0, 1, 2, 3, 4)
    assert spec.layers_of(hybrid.WINDOW) == ()


# -- (6) what the family does not serve yet, this spec too -----------------------


@pytest.mark.parametrize("feature,kw_", [
    ("prefix cache", dict(prefix_slots=2)),
    ("speculation", dict(speculate_k=2)),
    ("int8 pool", dict(kv_dtype="int8")),
    ("tensor parallelism", dict(tensor_parallel=2)),
    ("contiguous cache", dict(page_size=0, num_pages=0)),
])
def test_unsupported_features_are_refused_by_name(weights, feature, kw_):
    with pytest.raises(ValueError, match=feature):
        engine(weights, **kw_)


def test_handoff_is_refused_by_name(weights):
    eng = engine(weights)
    with pytest.raises(ValueError, match="handed off"):
        Scheduler(eng, role="prefill")
    with pytest.raises(NotImplementedError, match="hand-off"):
        eng.dump_slot_pages(0)


# -- (7) the neighbours disagree --------------------------------------------------


@pytest.mark.parametrize("swap", ["scale", "inner_norm", "yarn", "shared",
                                  "route_scale"])
def test_a_swapped_mechanism_disagrees_with_the_reference(weights, swap,
                                                          monkeypatch):
    """The program with the absorbed width's scale (``32 ** -0.5`` here,
    ``576 ** -0.5`` at the published widths) in place of the published
    one, with the two inner norms dropped, with the rotary left
    unstretched, without the shared expert or without the routing factor,
    no longer matches the reference."""
    tokens = np.arange(24, dtype=np.int32)[None] % SPEC.vocab
    want = reference_logits(weights, tokens[0])
    good, _ = hybrid.apply_hybrid(weights, jnp.asarray(tokens), SPEC)
    np.testing.assert_allclose(good[0], want, atol=TOL)
    bad, bad_weights = SPEC, weights
    if swap == "scale":
        monkeypatch.setattr(hybrid.HybridSpec, "latent_scale", property(
            lambda s: s.latent_row ** -0.5))
    elif swap == "inner_norm":
        norm = hybrid.rms_norm
        monkeypatch.setattr(hybrid, "rms_norm", lambda x, g, eps: x if
                            g.shape[-1] != SPEC.d_model else norm(x, g, eps))
    elif swap == "yarn":
        bad, scale = dataclasses.replace(SPEC, rope_factor=1.0), \
            SPEC.latent_scale
        monkeypatch.setattr(hybrid.HybridSpec, "latent_scale", property(
            lambda s: scale))  # the frequencies alone
    elif swap == "shared":
        blocks = [dict(b, sd=jnp.zeros_like(b["sd"])) if "sd" in b else b
                  for b in weights["blocks"]]
        bad_weights = dict(weights, blocks=blocks)
    else:
        bad = dataclasses.replace(SPEC, route_scale=None)
    got, _ = hybrid.apply_hybrid(bad_weights, jnp.asarray(tokens), bad)
    assert np.abs(np.asarray(got[0]) - want).max() > 100 * TOL
