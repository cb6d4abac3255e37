"""The dense serve engine holds its weights in the compute dtype.

``InferenceEngine._place`` casts every leaf to ``compute_dtype`` once,
on the device, as it places the tree (after the placement, so a tp
shard keeps its PartitionSpec), whichever way the weights arrive: the
constructor's own init, ``params=``, ``load_params``,
``from_checkpoint``. No program casts a weight again: the in-program
``astype`` of ``apply_lm_paged`` / ``apply_lm_cached`` is nothing on a
bf16 leaf. The products read the same bf16 values as when each program
cast fp32 weights itself, so logits and tokens are that form's bit for
bit; an engine handed fp32 weights directly (``engine.params = ...``)
is that form, and the oracle here. ``compute_dtype=None`` keeps fp32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.models.transformer import TINY_SPEC, init_lm_params
from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig
from ddl_tpu.serve.router import Router, RouterConfig

SPEC = TINY_SPEC
PAGED = dict(spec=SPEC, slots=2, capacity=32, page_size=8, num_pages=16)


def _host(seed=3):
    return jax.device_get(init_lm_params(jax.random.PRNGKey(seed), SPEC))


def _dtypes(tree):
    return {leaf.dtype for leaf in jax.tree.leaves(tree)}


@pytest.mark.parametrize("dtype,tp", [(None, 1), ("bfloat16", 1),
                                      ("bfloat16", 2)])
def test_engine_places_weights_in_the_compute_dtype(dtype, tp):
    """Every placed leaf is in the compute dtype (fp32 where none is
    set), sharded as the programs take it, and equal to the handed-over
    value rounded once to nearest-even."""
    host = _host()
    eng = InferenceEngine(ServeConfig(**PAGED, compute_dtype=dtype,
                                      tensor_parallel=tp), params=host)
    want = jnp.dtype(dtype or jnp.float32)
    assert _dtypes(eng.params) == {want}
    specs = jax.tree.leaves(eng._pspecs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec))
    for leaf, spec, given in zip(jax.tree.leaves(eng.params), specs,
                                 jax.tree.leaves(host)):
        assert leaf.sharding.spec == spec
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(given).astype(want))
    # The constructor's own init is placed the same way.
    fresh = InferenceEngine(ServeConfig(**PAGED, compute_dtype=dtype,
                                        tensor_parallel=tp))
    assert _dtypes(fresh.params) == {want}


def test_checkpoint_loads_place_bf16_leaves(tmp_path):
    """An fp32 params checkpoint comes in through ``load_params`` and
    ``from_checkpoint`` as bf16 leaves, the checkpoint's values rounded
    once."""
    from ddl_tpu.utils.checkpoint import save_checkpoint

    host = _host(seed=5)
    path = str(tmp_path / "params.npz")
    save_checkpoint(path, host)
    cfg = ServeConfig(**PAGED, compute_dtype="bfloat16")
    built = InferenceEngine.from_checkpoint(cfg, path)
    loaded = InferenceEngine(cfg)
    loaded.load_params(path)
    for eng in (built, loaded):
        assert _dtypes(eng.params) == {jnp.dtype(jnp.bfloat16)}
        for leaf, given in zip(jax.tree.leaves(eng.params),
                               jax.tree.leaves(host)):
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(given).astype(jnp.bfloat16))


@pytest.mark.parametrize("page_size,tp", [(8, 1), (0, 1), (8, 2)])
def test_bf16_weights_serve_the_in_program_casts_logits_bitwise(page_size,
                                                                  tp):
    """Prefill logits, decode logits and a greedy generation of an
    engine holding bf16 weights are bitwise those of the same engine
    handed the fp32 tree, whose programs cast it inside (the form before
    weights were held in the compute dtype), on the paged and the
    contiguous cache."""
    host = _host(seed=7)
    kw = dict(PAGED, page_size=page_size, num_pages=16 if page_size else 0)
    cfg = ServeConfig(**kw, compute_dtype="bfloat16", tensor_parallel=tp)
    held = InferenceEngine(cfg, params=host)
    cast_inside = InferenceEngine(cfg, params=host)
    cast_inside.params = jax.tree.map(
        lambda given, placed: jax.device_put(given, placed.sharding),
        host, held.params)
    assert _dtypes(held.params) == {jnp.dtype(jnp.bfloat16)}
    assert _dtypes(cast_inside.params) == {jnp.dtype(jnp.float32)}

    prompt = (np.arange(1, 12) * 5) % SPEC.vocab
    first = []
    for eng in (held, cast_inside):
        nxt, logits = eng.prefill(prompt, slot=0, request_id=1,
                                  want_logits=True)
        ids, step = eng.decode([nxt, 0], [len(prompt), 0], [1, 0],
                               [True, False], want_logits=True)
        first.append((nxt, logits, ids, step))
    (a_nxt, a_pre, a_ids, a_dec), (b_nxt, b_pre, b_ids, b_dec) = first
    assert a_nxt == b_nxt
    np.testing.assert_array_equal(a_pre, b_pre)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_dec, b_dec)

    reqs = lambda: [Request(id=i, prompt=(np.arange(3 + 2 * i) * (i + 3))
                            % SPEC.vocab, max_new_tokens=6)
                    for i in range(3)]
    tokens = []
    for eng in (held, cast_inside):
        eng.reset()
        done, _ = Scheduler(eng).run(reqs())
        tokens.append({i: c.tokens for i, c in done.items()})
    assert tokens[0] == tokens[1] and len(tokens[0]) == 3


def test_router_replicas_share_the_bf16_tree():
    """Replica 0 places the tree in bf16; every other replica serves
    those very arrays, not a copy."""
    cfg = ServeConfig(**PAGED, compute_dtype="bfloat16")
    router = Router(RouterConfig(serve=cfg, replicas=2), params=_host())
    first, second = (e.params for e in router.engines)
    assert _dtypes(first) == {jnp.dtype(jnp.bfloat16)}
    assert all(a is b for a, b in zip(jax.tree.leaves(first),
                                      jax.tree.leaves(second)))
