"""What a serve engine hands back: the sampled ids, and logits only to a
caller that asks in that call (``serve.engine_iface``).

The scheduler never asks, so its prefill program applies the head to the
last real row alone (no ``[bucket, vocab]`` value exists in it) and
nothing but the ids leaves the device; ``want_logits=True`` runs the
all-rows prefill form, a program of its own that ``Scheduler.warmup``
never builds. Asking changes no served token.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.models import hybrid
from ddl_tpu.models.transformer import LMSpec
from ddl_tpu.obs import MetricRegistry
from ddl_tpu.serve import Request, Scheduler, ServeConfig, engine_cls
from ddl_tpu.serve.engine import pack_args
from ddl_tpu.serve.sim import CostModelEngine

# A vocabulary no other width of the model equals: "<bucket>x48x" in a
# program's text can only be a [bucket, vocab] value.
SPEC = LMSpec(vocab=48, d_model=32, num_heads=2, num_layers=2, d_ff=64)
BUCKET = 16
LAYOUTS = {
    "paged": dict(page_size=8, num_pages=24),
    "contiguous": dict(),
}
LATENT_SPEC = hybrid.HybridSpec(
    head_dim=24, q_lora_rank=24, kv_lora_rank=16, nope_dim=8, rope_dim=16,
    rope_factor=4.0, rope_original=64, rope_beta_fast=8.0,
    rope_mscale_all_dim=1.0, shared_ff=16, route_scale=2.5,
    experts_held=(4, 8), layer_kinds=(hybrid.LATENT,) * 2,
    ffn_kinds=(hybrid.DENSE, hybrid.MOE))


def _engine(layout="paged", spec=SPEC, **kw):
    cfg = dict(spec=spec, slots=4, capacity=64, **LAYOUTS[layout])
    cfg.update(kw)
    return engine_cls(spec)(ServeConfig(**cfg))


def _prompt(n, seed, vocab=SPEC.vocab):
    return np.random.default_rng(seed).integers(
        1, vocab, size=n, dtype=np.int32)


def _prefill_text(eng, all_rows):
    """The lowered text of one prefill program at ``BUCKET``."""
    build = eng._prefill_paged_fn if eng.paged else eng._prefill_fn
    where = eng.tables[:1] if eng.paged else 0
    packed = pack_args((np.zeros((1, BUCKET), np.int32), 5, 0, where, 0))
    return build(BUCKET, all_rows).lower(
        eng.params, eng.cache, jnp.asarray(packed)).as_text()


def _asking(eng):
    """``eng`` with every call asking for logits, as a direct caller
    that reads them would; the scheduler drives it unchanged."""
    prefill, decode = eng.prefill, eng.decode
    eng.prefill = lambda *a, **k: prefill(*a, **k, want_logits=True)
    eng.decode = lambda *a, **k: decode(*a, **k, want_logits=True)
    return eng


# -- (a) the scheduler's prefill program holds no [bucket, vocab] value -------


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_scheduler_prefill_program_forms_one_row_of_logits(layout):
    eng = _engine(layout)
    rows = f"{BUCKET}x{SPEC.vocab}x"
    assert rows not in _prefill_text(eng, all_rows=False)
    assert rows in _prefill_text(eng, all_rows=True)  # the check can see one
    assert set(eng._prefill_fns) == set(eng._prefill_rows_fns) == {BUCKET}
    names = {eng._prefill_fns[BUCKET].__name__,
             eng._prefill_rows_fns[BUCKET].__name__}
    tag = "b" if layout == "paged" else "c"
    assert names == {f"run_prefill_{tag}{BUCKET}",
                     f"run_prefill_rows_{tag}{BUCKET}"}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_no_logits_reach_the_host_unless_asked(layout, monkeypatch):
    """Of a call that does not ask, the host fetches the sampled ids and
    nothing else: every array ``_call`` brings over is counted."""
    from ddl_tpu.serve import engine as engine_mod

    eng = _engine(layout)
    fetched = []

    class Counting:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kw):
            out = np.asarray(a, *args, **kw)
            if hasattr(a, "devices"):  # a device array comes to the host
                fetched.append(out.shape)
            return out

    monkeypatch.setattr(engine_mod, "np", Counting())
    S, V = eng.config.slots, SPEC.vocab
    prompt = _prompt(11, 0)
    tok, none = eng.prefill(prompt, slot=1, request_id=3)
    assert none is None and fetched == [()]
    last, lengths = np.zeros(S, np.int32), np.zeros(S, np.int32)
    active = np.zeros(S, bool)
    last[1], lengths[1], active[1] = tok, len(prompt), True
    nxt, none = eng.decode(last, lengths, np.full(S, 3, np.int32), active)
    assert none is None and fetched == [(), (S,)] and nxt.shape == (S,)
    # asked: the same tokens, and the logits behind them
    del fetched[:]
    eng.reset()
    tok2, logits = eng.prefill(prompt, slot=1, request_id=3, want_logits=True)
    assert tok2 == tok and logits.shape == (len(prompt), V)
    assert fetched == [(), (BUCKET, V)]
    nxt2, logits = eng.decode(last, lengths, np.full(S, 3, np.int32), active,
                              want_logits=True)
    assert logits.shape == (S, V) and fetched[2:] == [(S,), (S, V)]
    np.testing.assert_array_equal(nxt2[active], nxt[active])
    assert int(nxt2[1]) == int(np.argmax(logits[1]))


def test_twin_follows_the_same_contract():
    eng = CostModelEngine(ServeConfig(spec=SPEC, slots=2, capacity=32,
                                      page_size=8))
    S = eng.config.slots
    zeros = np.zeros(S, np.int32)
    tok, none = eng.prefill(_prompt(5, 1), slot=0, request_id=0)
    assert none is None
    assert eng.decode(zeros, zeros, zeros, np.zeros(S, bool))[1] is None
    eng.release_slot(0)
    tok2, logits = eng.prefill(_prompt(5, 1), slot=0, request_id=0,
                               want_logits=True)
    assert tok2 == tok and logits.shape == (5, SPEC.vocab)
    assert eng.decode(zeros, zeros, zeros, np.zeros(S, bool),
                      want_logits=True)[1].shape == (S, SPEC.vocab)


# -- (b) asking changes no served token ---------------------------------------


CASES = {
    "paged": dict(layout="paged"),
    "contiguous": dict(layout="contiguous"),
    "int8": dict(layout="paged", kv_dtype="int8"),
    "tp2": dict(layout="paged", tensor_parallel=2),
    "chunked_prefix": dict(layout="paged", prefill_chunk=8, prefix_slots=2),
    "speculate": dict(layout="paged", speculate_k=2),
    "window_global": dict(layout="paged", spec=hybrid.HybridSpec(),
                          page_size=4, num_pages=64),
    "latent": dict(layout="paged", spec=LATENT_SPEC, page_size=4,
                   num_pages=64),
}


@pytest.mark.parametrize("case", list(CASES))
def test_served_tokens_are_the_same_with_and_without_logits(case):
    """A whole ``Scheduler.run`` over more requests than slots, once as
    the scheduler calls the engine and once with every call asking."""
    kw = dict(CASES[case])
    vocab = kw.get("spec", SPEC).vocab
    shared = _prompt(9, 99, vocab)  # a prefix to hit, a stream to draft from
    reqs = [Request(id=i, max_new_tokens=m, prompt=np.concatenate(
                [shared, _prompt(n, i, vocab)]))
            for i, (n, m) in enumerate([(10, 7), (1, 12), (24, 5), (3, 9),
                                        (17, 6), (6, 8)])]
    plain, reg = _engine(**kw), MetricRegistry()
    done, stats = Scheduler(plain, eos_id=None, registry=reg).run(reqs)
    asked, _ = Scheduler(_asking(_engine(**kw)), eos_id=None).run(reqs)
    assert all(done[r.id].status == "ok" for r in reqs)
    if case == "speculate":  # lanes rode: the verifier's path was taken
        assert reg.counter("speculate_proposed_total").value() > 0
    if case == "chunked_prefix":
        assert stats.prefix_hits > 0
    assert {i: asked[i].tokens for i in asked} == \
        {i: done[i].tokens for i in done}
    assert not plain._prefill_rows_fns  # the plain run built none


# -- (c) the one row is the all-rows form's last real row ---------------------


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("t", [1, 11, 16])
def test_one_row_prefill_logits_equal_the_last_real_row(layout, t):
    """Two programs, so an absolute tolerance and no bitwise pin; the
    scheduler form's own ``[1, vocab]`` output is fetched by hand."""
    eng = _engine(layout)
    seen = {}
    call = eng._call

    def fetching(kind, fn, args, want_logits):
        out, seen["logits"] = call(kind, fn, args, True)
        return out, seen["logits"]

    eng._call = fetching
    prompt = _prompt(t, 4)
    tok, none = eng.prefill(prompt, slot=2, request_id=5, _bucket=BUCKET)
    assert none is None and seen["logits"].shape == (1, SPEC.vocab)
    one = seen["logits"][0]
    eng.reset()
    tok_rows, rows = eng.prefill(prompt, slot=2, request_id=5,
                                 _bucket=BUCKET, want_logits=True)
    assert rows.shape == (t, SPEC.vocab)
    np.testing.assert_allclose(one, rows[t - 1], rtol=0, atol=1e-5)
    assert tok == tok_rows == int(np.argmax(one))


# -- (d) warm-up builds the scheduler's programs alone ------------------------


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_warmup_builds_no_all_rows_program(layout):
    reg = MetricRegistry()
    eng = _engine(layout)
    sched = Scheduler(eng, eos_id=None, registry=reg)
    reqs = [Request(id=i, prompt=_prompt(n, i), max_new_tokens=4)
            for i, n in enumerate([5, 20, 40])]
    sched.warmup(reqs)
    built = reg.counter("xla_compiles_total")
    assert built.value(kind="prefill") == len(eng._prefill_fns) == 4
    assert built.value(kind="prefill_rows") == 0 == len(eng._prefill_rows_fns)
    sched.run(reqs)
    assert built.value(kind="prefill") == 4  # the run compiled nothing more
    assert built.value(kind="prefill_rows") == 0
    # the first caller that asks pays for its own program, once a bucket
    for _ in range(2):
        eng.prefill(_prompt(5, 0), slot=0, request_id=9, want_logits=True)
        if eng.paged:
            eng.release_slot(0)
    assert built.value(kind="prefill_rows") == 1
    assert set(eng._prefill_rows_fns) == {8}
