"""Speculative decoding (ddl_tpu/serve/speculate.py, ISSUE 15).

The acceptance chain: greedy-accept speculative decode (k in {2, 4})
produces tokens AND per-accepted-step logits BIT-IDENTICAL to plain
greedy decode at tp=1 AND tp=2 — the verify rides FREE SLOTS of the one
batched decode call (draft lanes over page-aliased tables), so every
verified row is the SAME compiled program computing the same
row-independent math. ``speculate_accepted_total`` /
``speculate_proposed_total`` give a measured acceptance rate, and
``speculate_k=0`` compiles the byte-identical pre-speculation decode
program (HLO-text pinned) with the Python branch fully off-path.
"""

import numpy as np
import pytest

from ddl_tpu.models.transformer import TINY_SPEC
from ddl_tpu.obs import MetricRegistry
from ddl_tpu.serve import (
    InferenceEngine,
    Request,
    Scheduler,
    ServeConfig,
    greedy_accept,
    propose_draft,
)
from ddl_tpu.serve.engine import pack_args

SPEC = TINY_SPEC


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, SPEC.vocab, size=n, dtype=np.int32)


def _record_decode_rows(eng, rows):
    """Record every ACTIVE slot's logits row keyed by (request_id,
    lengths) — the (request, token-index) coordinate both plain decode
    and the draft lanes use, so the same recorder aligns the two runs.
    Last write wins: a rejected lane's row is recomputed (correctly) by
    the later step that actually emits that position."""
    d0 = eng.decode

    def dec(last, lengths, rids, act, **kw):
        nxt, lg = d0(last, lengths, rids, act, **kw, want_logits=True)
        lg = np.asarray(lg)
        for s in range(len(act)):
            if act[s]:
                rows[(int(rids[s]), int(lengths[s]))] = lg[s].copy()
        return nxt, lg

    eng.decode = dec


def test_propose_draft_lookup_semantics():
    """The matcher: longest suffix n-gram first, RIGHTMOST earlier
    occurrence, draft truncated to k and to what the source holds;
    'prompt' restricts the source to the prompt window; no match is an
    empty draft, not an error."""
    ctx = np.asarray([1, 5, 6, 7, 9, 5, 6, 7], np.int32)
    # Suffix (5,6,7) matched at position 1; the continuation runs on
    # through the source: [9, 5, 6, 7], truncated by k.
    np.testing.assert_array_equal(propose_draft(ctx, 4), [9, 5, 6, 7])
    np.testing.assert_array_equal(propose_draft(ctx, 2), [9, 5])
    # Rightmost match wins: two earlier (2,3) occurrences, the later
    # one's continuation is proposed.
    ctx2 = np.asarray([2, 3, 4, 2, 3, 8, 2, 3], np.int32)
    np.testing.assert_array_equal(propose_draft(ctx2, 2), [8, 2])
    # k truncates.
    np.testing.assert_array_equal(propose_draft(ctx2, 1), [8])
    # prompt-only lookup ignores the generated tail.
    ctx3 = np.asarray([4, 5, 9, 9, 4, 5], np.int32)
    np.testing.assert_array_equal(
        propose_draft(ctx3, 2, method="prompt", prompt_len=4), [9, 9]
    )
    # No recurring suffix: empty.
    assert propose_draft(np.arange(1, 7, dtype=np.int32), 3).size == 0
    assert propose_draft(ctx, 0).size == 0
    with pytest.raises(ValueError, match="unknown speculate method"):
        propose_draft(ctx, 2, method="beam")
    with pytest.raises(ValueError, match="prompt_len"):
        propose_draft(ctx, 2, method="prompt")
    # Acceptance rule: longest matching prefix, pure arithmetic.
    assert greedy_accept([3, 4], [3, 4, 9]) == 2
    assert greedy_accept([3, 7], [3, 4, 9]) == 1
    assert greedy_accept([8], [3, 4]) == 0
    assert greedy_accept([], [3]) == 0


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("k", [2, 4])
def test_speculative_decode_bit_identical(tp, k):
    """THE speculation pin: speculative greedy decode emits the SAME
    tokens as plain greedy decode AND, per accepted step, the SAME
    logits row bitwise — at tp=1 and tp=2, k=2 and k=4 (draft lanes are
    the decode program's own row-independent math). The pool reads
    byte-whole afterwards (lane aliases are pure incref/decref)."""
    cfg = ServeConfig(spec=SPEC, slots=4, capacity=64, page_size=8,
                      num_pages=24, tensor_parallel=tp)
    reqs = [Request(id=i, prompt=_prompt(8, i), max_new_tokens=12)
            for i in range(2)]

    rows_plain, rows_spec = {}, {}
    eng_p = InferenceEngine(cfg)
    _record_decode_rows(eng_p, rows_plain)
    done_p, stats_p = Scheduler(eng_p).run(reqs)

    import dataclasses

    reg = MetricRegistry()
    eng_s = InferenceEngine(dataclasses.replace(cfg, speculate_k=k))
    _record_decode_rows(eng_s, rows_spec)
    done_s, stats_s = Scheduler(eng_s, registry=reg).run(reqs)

    assert {i: done_s[i].tokens for i in done_s} == \
        {i: done_p[i].tokens for i in done_p}
    # Every (request, token-index) logits row the plain run produced
    # exists in the speculative run — bitwise equal (the speculative
    # run may hold EXTRA rows: lanes computed past an eos/finish).
    for key, row in rows_plain.items():
        np.testing.assert_array_equal(row, rows_spec[key])
    # The acceptance ledger measured a real rate.
    prop = int(reg.counter("speculate_proposed_total").value())
    acc = int(reg.counter("speculate_accepted_total").value())
    assert prop > 0 and 0 <= acc <= prop
    # Same emitted tokens, fewer (or equal) target-model steps — the
    # whole point of the lanes.
    assert stats_s.decode_tokens == stats_p.decode_tokens
    assert stats_s.decode_steps <= stats_p.decode_steps
    for eng in (eng_p, eng_s):
        assert eng.pages.free == eng.num_pages
        assert eng.pages.reserved == 0


def test_speculate_accepts_on_looping_stream():
    """Greedy decode of the tiny model settles into a token loop; the
    n-gram draft nails the loop, so a long-enough run ACCEPTS drafts
    and emits more than one token per target step — the decode-
    throughput lever measured end-to-end (seeded, deterministic)."""
    cfg = ServeConfig(spec=SPEC, slots=4, capacity=64, page_size=8,
                      num_pages=24, speculate_k=4)
    reg = MetricRegistry()
    eng = InferenceEngine(cfg)
    done, stats = Scheduler(eng, registry=reg).run(
        [Request(id=0, prompt=_prompt(8, 0), max_new_tokens=16)]
    )
    acc = int(reg.counter("speculate_accepted_total").value())
    assert acc >= 1
    assert len(done[0].tokens) == 16
    # Decode emits max_new - 1 tokens (the first came from prefill) in
    # FEWER calls: more than one emitted token per target step.
    assert stats.decode_tokens == 15
    assert stats.decode_tokens / stats.decode_steps > 1.0


def test_speculate_k0_compiles_byte_identical_program():
    """The off-path pin: speculation adds NO program shapes — the k=4
    engine's decode program lowers to byte-identical HLO text as the
    k=0 engine's (config rides only the Python branch), and a k=0 run
    never consults the draft machinery at all (propose_draft poisoned
    under it runs clean)."""
    import dataclasses

    import jax.numpy as jnp

    base = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                       num_pages=8)
    texts = []
    for cfg in (base, dataclasses.replace(base, speculate_k=4)):
        eng = InferenceEngine(cfg)
        S = cfg.slots
        zeros = np.zeros(S, np.int32)
        packed = pack_args((zeros, zeros, zeros, np.zeros(S, bool),
                            np.zeros((S, 1), np.int32)))
        lowered = eng._decode_paged(1).lower(
            eng.params, eng.cache, jnp.asarray(packed))
        texts.append(lowered.as_text())
    assert texts[0] == texts[1]

    from ddl_tpu.serve import scheduler as sched_mod

    def boom(*a, **kw):  # pragma: no cover - the pin is it never runs
        raise AssertionError("propose_draft consulted with speculate_k=0")

    orig = sched_mod.propose_draft
    sched_mod.propose_draft = boom
    try:
        eng = InferenceEngine(base)
        done, _ = Scheduler(eng).run(
            [Request(id=0, prompt=_prompt(6, 1), max_new_tokens=3)]
        )
        assert done[0].status == "ok"
    finally:
        sched_mod.propose_draft = orig


def test_speculate_config_validation_is_loud():
    """Loud-ctor discipline: every structural requirement of the lane
    design is a named config error, never a silent no-speculate or a
    mid-run lane failure."""
    with pytest.raises(ValueError, match="paged KV layout"):
        InferenceEngine(ServeConfig(spec=SPEC, speculate_k=2))
    with pytest.raises(ValueError, match="temperature=0"):
        InferenceEngine(ServeConfig(spec=SPEC, page_size=8,
                                    capacity=32, speculate_k=2,
                                    temperature=0.7))
    with pytest.raises(ValueError, match="slots >= 2"):
        InferenceEngine(ServeConfig(spec=SPEC, slots=1, page_size=8,
                                    capacity=32, speculate_k=2))
    with pytest.raises(ValueError, match="speculate_method"):
        InferenceEngine(ServeConfig(spec=SPEC, speculate_method="beam"))
    with pytest.raises(ValueError, match="speculate_k must be >= 0"):
        InferenceEngine(ServeConfig(spec=SPEC, speculate_k=-1))


def test_speculate_full_occupancy_degrades_to_plain():
    """No free slots, no lanes: a fully-occupied speculative batch
    serves plain decode's exact tokens with zero proposals — the
    documented "when k hurts" degradation is graceful, not an error."""
    cfg = ServeConfig(spec=SPEC, slots=2, capacity=32, page_size=8,
                      num_pages=12)
    reqs = [Request(id=i, prompt=_prompt(6, i), max_new_tokens=4)
            for i in range(2)]
    eng_p = InferenceEngine(cfg)
    done_p, _ = Scheduler(eng_p).run(reqs)

    import dataclasses

    reg = MetricRegistry()
    eng_s = InferenceEngine(dataclasses.replace(cfg, speculate_k=2))
    done_s, _ = Scheduler(eng_s, registry=reg).run(reqs)
    assert {i: done_s[i].tokens for i in done_s} == \
        {i: done_p[i].tokens for i in done_p}
    # Both slots occupied every decode tick: no lane ever existed.
    assert reg.get("speculate_proposed_total") is None
