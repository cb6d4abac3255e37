"""Decode attention through the block table (ISSUE 31).

``ops.paged_attention.paged_decode_attention`` reads each slot's pages
where they lie in the stacked pool; here it runs in Pallas interpret
mode on the CPU against the path it replaces on the chip, ``gather_pages``
+ ``attend`` over the gathered views, on small stacked pools. What the
block table can hold is the cases: a partly filled last page, a slot
with no page, a bucket wider than every slot, prefix pages two slots
share, two lanes over one slot's pages at different positions, stale
rows of a reused page, a hole in a table. Then the rule by which
``apply_lm_paged`` chooses between the two, from what the shapes show.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl_tpu.ops import kv_cache
from ddl_tpu.ops.kv_cache import PAD_POS
from ddl_tpu.ops.paged_attention import (kernel_accepts,
                                         paged_decode_attention)

L, P, S, H, D = 3, 12, 16, 8, 128
B, TP = 4, 4


def _case(name):
    """``(table [B, TP], pool_pos [P, S], q_pos [B], empty slots)``. A
    slot is a list of pages and the tokens it holds; its rows carry
    positions 0.. in table order, the rest of its last page ``PAD_POS``."""
    pos = np.full((P, S), PAD_POS, np.int32)
    table = np.full((B, TP), -1, np.int32)
    q_pos = np.zeros(B, np.int32)

    def fill(slot, pages, length, at=None):
        table[slot, :len(pages)] = pages
        for i, page in enumerate(pages):
            rows = min(S, length - i * S)
            pos[page, :rows] = i * S + np.arange(rows)
        q_pos[slot] = length - 1 if at is None else at

    fill(1, [1], S)
    fill(3, [2, 4], S + 3)
    empty = []
    if name == "partly_filled_last_page":
        fill(0, [3, 5, 7], 2 * S + 9)
        fill(2, [9], 1)
    elif name == "slot_without_pages":
        fill(0, [3, 5], 2 * S)
        empty = [2]
    elif name == "table_wider_than_every_slot":
        fill(0, [3, 5], S + 1)
        fill(2, [9], 7)
    elif name == "shared_prefix_pages":
        fill(0, [3, 5, 7], 2 * S + 4)
        fill(2, [3, 5, 8], 2 * S + 11)
    elif name == "lanes_aliasing_one_slot":
        fill(0, [3, 5, 7], 2 * S + 9, at=S + 2)
        fill(2, [3, 5, 7], 2 * S + 9, at=2 * S + 6)
    elif name == "stale_rows_of_a_reused_page":
        fill(0, [3, 5], S + 5)
        pos[5, 5:12] = 100 + np.arange(7)   # the page's last holder's
        fill(2, [9], 3)
        pos[9, 3:] = 40 + np.arange(S - 3)
    elif name == "hole_in_the_table":
        fill(0, [3, 5, 7], 2 * S + 9)
        table[0, 1] = -1                     # the gathered path masks it too
        fill(2, [9], 2)
    else:
        raise KeyError(name)
    return table, pos, q_pos, empty


def _gathered(q, pool_k, pool_v, pos, table, q_pos, layer):
    """The path the kernel replaces, in fp32."""
    f32 = lambda a: a.astype(jnp.float32)
    return kv_cache.attend(
        f32(q)[:, None],
        f32(kv_cache.gather_pages(pool_k, table, layer=layer)),
        f32(kv_cache.gather_pages(pool_v, table, layer=layer)),
        q_pos[:, None], kv_cache.table_positions(pos, table))[:, 0]


@pytest.fixture(scope="module")
def kernel():
    """One traced program a dtype: the layer is an operand."""
    return jax.jit(lambda *a: paged_decode_attention(*a, interpret=True))


@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", [
    "partly_filled_last_page", "slot_without_pages",
    "table_wider_than_every_slot", "shared_prefix_pages",
    "lanes_aliasing_one_slot", "stale_rows_of_a_reused_page",
    "hole_in_the_table",
])
def test_kernel_matches_gather_and_attend(kernel, case, dtype, tol, layer):
    table, pos, q_pos, empty = _case(case)
    keys = jax.random.split(jax.random.PRNGKey(31), 3)
    pool_k, pool_v = (jax.random.normal(k, (L, P, S, H, D), dtype)
                      for k in keys[:2])
    q = jax.random.normal(keys[2], (B, H, D), dtype)
    args = (q, pool_k, pool_v, jnp.asarray(pos), jnp.asarray(table),
            jnp.asarray(q_pos))
    got = kernel(*args, jnp.int32(layer))
    assert got.dtype == q.dtype and got.shape == (B, H, D)
    got = np.asarray(got, np.float32)
    want = np.asarray(_gathered(*args, layer))
    live = [b for b in range(B) if b not in empty]
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=0)
    # A slot with no page reads nothing: finite, and dropped by the caller.
    assert (got[empty] == 0).all()
    # ...and the layer asked for is the layer read.
    other = np.asarray(_gathered(*args, (layer + 1) % L))
    assert np.abs(other[live] - want[live]).max() > 10 * tol


def _lowered_forward(t, int8, heads, platform):
    """``apply_lm_paged`` traced over shapes alone: a decode (or ``t``
    tokens a slot) of 2 slots over a 2-layer stack of 4 pages."""
    from ddl_tpu.models.transformer import (LMSpec, apply_lm_paged,
                                            init_lm_params)

    spec = LMSpec(vocab=32, d_model=1024, num_heads=heads, num_layers=2,
                  d_ff=64)
    on = jax.ShapeDtypeStruct
    stack = (spec.num_layers, 4, S, heads)
    pool = on(stack + (spec.head_dim,), jnp.int8 if int8 else jnp.bfloat16)
    scales = (on(stack, jnp.float32),) * 2 if int8 else (None, None)

    def forward(params, pool_k, pool_v, pool_pos, k_scale, v_scale, tokens,
                table, positions, flat_rows):
        return apply_lm_paged(
            params, tokens, pool_k, pool_v, pool_pos, table, spec,
            positions=positions, flat_rows=flat_rows,
            compute_dtype=jnp.bfloat16, pool_k_scale=k_scale,
            pool_v_scale=v_scale, platform=platform)

    i32 = lambda *shape: on(shape, jnp.int32)
    return forward, (
        jax.eval_shape(lambda: init_lm_params(jax.random.PRNGKey(0), spec)),
        pool, pool, i32(4, S), *scales, i32(2, t), i32(2, 2), i32(2, t),
        i32(2, t))


@pytest.mark.parametrize("why,t,int8,heads,platform", [
    ("two_queries_a_slot", 2, False, 8, "tpu"),
    ("int8_scale_planes", 1, True, 8, "tpu"),
    ("head_dim_64", 1, False, 16, "tpu"),
    ("platform_cpu", 1, False, 8, "cpu"),
    ("platform_unnamed_on_a_cpu_backend", 1, False, 8, None),
])
def test_apply_lm_paged_gathers_where_the_kernel_does_not_fit(
        why, t, int8, heads, platform):
    """One query a slot, no scale planes, heads of whole lane tiles and
    a TPU: short of any one of them the forward gathers its views and
    calls ``attend``, and its lowered text holds no kernel."""
    forward, shapes = _lowered_forward(t, int8, heads, platform)
    assert "paged_decode_attention" not in str(
        jax.make_jaxpr(forward)(*shapes))
    text = jax.jit(forward).lower(*shapes).as_text()
    assert "paged_decode_attention" not in text
    assert "tpu_custom_call" not in text


def test_apply_lm_paged_reads_pages_in_place_where_the_kernel_fits():
    """All four hold: one call a layer of ONE traced kernel (the layer
    is an operand of a jitted function, so a program lowers the kernel
    once however many layers it has), and no gathered view (``[2 slots,
    2 pages x 16 rows, 8, 128]``) in the trace."""
    forward, shapes = _lowered_forward(1, False, 8, "tpu")
    trace = str(jax.make_jaxpr(forward)(*shapes))
    assert len(re.findall(r"jit\[\s*name=paged_decode_attention",
                          trace)) == 2
    assert trace.count("pallas_call[") == 1
    assert "bf16[2,32,8,128]" not in trace
    gathered = str(jax.make_jaxpr(_lowered_forward(1, False, 8, "cpu")[0])(
        *shapes))
    assert "bf16[2,32,8,128]" in gathered


@pytest.mark.parametrize("heads,head_dim,page,fits", [
    (8, 256, 64, True), (8, 128, 16, True), (16, 128, 8, True),
    (8, 64, 64, False),    # chip_smoke's toy spec: half a lane tile
    (4, 256, 64, False),   # 8 heads over tp 2: half a sublane tile
    (8, 128, 8, False),    # a page of 64 scores: half a lane tile
])
def test_kernel_accepts_whole_tiles_only(heads, head_dim, page, fits):
    assert kernel_accepts(heads, head_dim, page) is fits


# -- the latent pool: one K/V head, V inside K (ISSUE 32) ----------------------
#
# ``latent_decode_attention`` against ``gather_pages`` + ``attend_grouped``
# over ONE K/V head whose V is the row's first ``v_width`` values: the path
# it replaces on the chip in ``models.hybrid``'s decode.

LP, LS, LW, LV, LH, LB, LTP = 24, 16, 256, 128, 8, 4, 6


def _latent_case(name):
    """``(table [LB, LTP], q_pos [LB], slots that read nothing)``; the
    last slot is always free."""
    table = np.full((LB, LTP), -1, np.int32)
    q_pos = np.full(LB, -1, np.int32)

    def fill(slot, pages, length):
        table[slot, :len(pages)] = pages
        q_pos[slot] = length - 1

    fill(1, [1], LS)
    if name == "partly_filled_last_page":
        fill(0, [3, 5, 7], 2 * LS + 9)
        fill(2, [9], 1)
    elif name == "slot_without_pages":
        fill(0, [3, 5], 2 * LS)
    elif name == "widest_slot_fills_the_bucket":
        fill(0, [2, 4, 6, 8, 10, 12], 6 * LS)
        fill(2, [9, 11], LS + 1)
    elif name == "inactive_slot_keeps_its_pages":
        fill(0, [3, 5, 7], 2 * LS + 9)
        table[2, :2] = [9, 11]   # mid-prefill: mapped, q_pos -1
    elif name == "hole_in_the_table":
        fill(0, [3, 5, 7], 2 * LS + 9)
        table[0, 1] = -1
        fill(2, [9], 2)
    else:
        raise KeyError(name)
    return table, q_pos, [b for b in range(LB) if q_pos[b] < 0]


@pytest.mark.parametrize("pages_per_step", [4, 3])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", [
    "partly_filled_last_page", "slot_without_pages",
    "widest_slot_fills_the_bucket", "inactive_slot_keeps_its_pages",
    "hole_in_the_table",
])
def test_latent_kernel_matches_gather_and_attend(case, dtype, tol,
                                                 pages_per_step):
    from ddl_tpu.ops.paged_attention import latent_decode_attention

    table, q_pos, empty = _latent_case(case)
    keys = jax.random.split(jax.random.PRNGKey(32), 2)
    pool = jax.random.normal(keys[0], (LP, LS, LW), dtype)
    q = jax.random.normal(keys[1], (LB, LH, LW), dtype)
    got = latent_decode_attention(
        q, pool, jnp.asarray(table), jnp.asarray(q_pos), scale=0.11,
        v_width=LV, pages_per_step=pages_per_step, interpret=True)
    assert got.dtype == q.dtype and got.shape == (LB, LH, LV)
    f32 = lambda a: a.astype(jnp.float32)
    view = f32(kv_cache.gather_pages(pool, jnp.asarray(table)))[:, :, None]
    cols = np.arange(LTP * LS)[None]
    k_pos = np.where((cols <= q_pos[:, None])
                     & (np.repeat(table, LS, axis=1) >= 0), cols, -1)
    want = kv_cache.attend_grouped(
        f32(q)[:, None], view, view[..., :LV], jnp.asarray(q_pos)[:, None],
        jnp.asarray(k_pos), scale=0.11)[:, 0]
    got = np.asarray(got, np.float32)
    live = [b for b in range(LB) if b not in empty]
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=tol,
                               rtol=0)
    assert (got[empty] == 0).all()


@pytest.mark.parametrize("heads,row,v,page,fits", [
    (64, 640, 512, 64, True), (8, 256, 128, 16, True),
    (64, 576, 512, 64, False),   # 4.5 lane tiles: the pool is 640 wide
    (4, 256, 128, 16, False),    # half a sublane tile of heads
    (8, 128, 16, 16, False),     # the toys' kv_lora: V under a lane tile
    (8, 256, 128, 8, False),     # a page under bf16's 16-row tile
])
def test_latent_kernel_accepts_whole_tiles_only(heads, row, v, page, fits):
    from ddl_tpu.ops.paged_attention import latent_kernel_accepts

    assert latent_kernel_accepts(heads, row, v, page) is fits


def _latent_decode(platform, monkeypatch=None):
    """One decode tick of ``models.hybrid``'s paged forward over latent
    layers whose widths the kernel takes (8 heads, rows of 144 in a pool
    of 256, V of 128, pages of 16), 3 slots of which one is inactive."""
    import functools

    from ddl_tpu.models import hybrid
    from ddl_tpu.ops import paged_attention
    from ddl_tpu.serve.cache import hybrid_cache

    spec = hybrid.HybridSpec(
        vocab=32, d_model=32, num_heads=8, head_dim=24, v_head_dim=8,
        q_lora_rank=16, kv_lora_rank=128, nope_dim=8, rope_dim=16,
        rope_factor=4.0, rope_original=64, rope_beta_fast=8.0,
        rope_mscale_all_dim=1.0, d_ff=32, layer_kinds=(hybrid.LATENT,) * 2,
        ffn_kinds=(hybrid.DENSE,) * 2)
    params = hybrid.init_hybrid_params(jax.random.PRNGKey(3), spec)
    cache = hybrid_cache(spec, 12, 0, 16, jnp.float32)
    pools = {i: (jax.random.normal(jax.random.PRNGKey(i), p.shape)
                 .at[..., spec.latent_row:].set(0.0), None)
             for i, p in enumerate(cache.k)}
    table = jnp.asarray([[3, 5, 7, -1], [1, -1, -1, -1], [9, 11, -1, -1]])
    active = jnp.asarray([True, True, False])
    positions = jnp.where(active, jnp.asarray([40, 9, 20]), -1)
    if monkeypatch is not None:
        monkeypatch.setattr(
            paged_attention, "latent_decode_attention", functools.partial(
                paged_attention.latent_decode_attention, interpret=True))

    def forward(params, pools, tokens):
        return hybrid.apply_hybrid_paged(
            params, pools, tokens, spec, page_size=16, g_table=table,
            w_table=None, positions=positions[:, None],
            real=active[:, None], last=positions, platform=platform)

    return forward, (params, pools, jnp.asarray([[1], [2], [3]]))


def test_hybrid_decode_reads_latent_pages_in_place_on_a_tpu(monkeypatch):
    """The rule ``PagedMixer`` chooses by: on a TPU, at widths the kernel
    takes, a latent layer's decode is one ``latent_decode_attention`` a
    layer (one traced kernel) and no gathered view; the CPU gathers. Both
    give the same rows, pools and hidden state."""
    forward, args = _latent_decode("tpu", monkeypatch)
    trace = str(jax.make_jaxpr(forward)(*args))
    assert len(re.findall(r"jit\[\s*name=latent_decode_attention",
                          trace)) == 2
    assert trace.count("pallas_call[") == 1
    assert "f32[3,64,256]" not in trace        # the gathered view
    plain, _ = _latent_decode("cpu")
    assert "f32[3,64,256]" in str(jax.make_jaxpr(plain)(*args))
    assert "latent_decode_attention" not in str(jax.make_jaxpr(plain)(*args))
    h, pools, _ = forward(*args)
    want_h, want_pools, _ = plain(*args)
    live = np.asarray([0, 1])
    np.testing.assert_allclose(np.asarray(h)[live], np.asarray(want_h)[live],
                               atol=2e-5)
    np.testing.assert_array_equal(pools[0][0], want_pools[0][0])
    np.testing.assert_allclose(pools[1][0], want_pools[1][0], atol=2e-5)


# -- the grouped pool: a K/V head a block, V beside K (ISSUE 35) ---------------
#
# ``grouped_decode_attention`` against ``gather_grouped`` + ``attend_grouped``:
# the path it replaces on the chip in ``models.hybrid``'s global layers.

# The latent cases' tables (a slot with one row, a slot without pages, an
# inactive slot that keeps its pages, a hole, a bucket the widest slot
# fills) over a pool of the same pages and rows.
GP, GS, GHKV, GGQ, GB, GTP = LP, LS, 2, 8, LB, LTP


@pytest.mark.parametrize("pages_per_step", [4, 3])
@pytest.mark.parametrize("dk,dv", [(192, 128), (128, 128)])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", [
    "partly_filled_last_page", "slot_without_pages",
    "widest_slot_fills_the_bucket", "inactive_slot_keeps_its_pages",
    "hole_in_the_table",
])
def test_grouped_kernel_matches_gather_and_attend(case, dtype, tol, dk, dv,
                                                  pages_per_step):
    """Rows ``[k | zeros | v]`` of K wider than V (192 / 128: 384) and of
    equal widths (256), 4 pages a step (a table of 6 is widened to 8)
    and 3, both K/V heads of a page in one block."""
    from ddl_tpu.ops.paged_attention import (gather_grouped,
                                             grouped_decode_attention,
                                             grouped_row_widths,
                                             grouped_rows)

    table, q_pos, empty = _latent_case(case)
    keys = jax.random.split(jax.random.PRNGKey(35), 3)
    k_width, width = grouped_row_widths(dk, dv)
    assert (k_width, width) == ((256, 384) if dk == 192 else (128, 256))
    pool = grouped_rows(jax.random.normal(keys[0], (GP, GHKV, GS, dk), dtype),
                        jax.random.normal(keys[1], (GP, GHKV, GS, dv), dtype))
    assert pool.shape == (GP, GHKV, GS, width)
    assert (np.asarray(pool[..., dk:k_width], np.float32) == 0).all()
    q = jax.random.normal(keys[2], (GB, GHKV, GGQ, dk), dtype)
    got = grouped_decode_attention(
        q, pool, jnp.asarray(table), jnp.asarray(q_pos), v_head_dim=dv,
        pages_per_step=pages_per_step, interpret=True)
    assert got.dtype == q.dtype and got.shape == (GB, GHKV, GGQ, dv)
    f32 = lambda a: a.astype(jnp.float32)
    kv, vv = gather_grouped(f32(pool), jnp.asarray(table), dk, dv)
    cols = np.arange(GTP * GS)[None]
    k_pos = np.where((cols <= q_pos[:, None])
                     & (np.repeat(table, GS, axis=1) >= 0), cols, -1)
    want = kv_cache.attend_grouped(
        f32(q).reshape(GB, 1, GHKV * GGQ, dk), kv, vv,
        jnp.asarray(q_pos)[:, None], jnp.asarray(k_pos))[:, 0]
    got = np.asarray(got, np.float32).reshape(GB, GHKV * GGQ, dv)
    live = [b for b in range(GB) if b not in empty]
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=tol,
                               rtol=0)
    assert (got[empty] == 0).all()


@pytest.mark.parametrize("group_heads,dk,dv,page,fits", [
    (16, 192, 128, 64, True), (8, 128, 128, 16, True),
    (16, 256, 192, 64, True),    # V filled to two lane tiles
    (16, 12, 8, 64, False),      # the toys' heads: a row mostly filling
    (16, 192, 64, 64, False),    # V under a lane tile
    (4, 192, 128, 64, False),    # half a sublane tile of query heads
    (16, 192, 128, 8, False),    # a page under bf16's 16-row tile
])
def test_grouped_kernel_accepts_whole_tiles_only(group_heads, dk, dv, page,
                                                 fits):
    from ddl_tpu.ops.paged_attention import grouped_kernel_accepts

    assert grouped_kernel_accepts(group_heads, dk, dv, page) is fits


def _global_decode(platform, monkeypatch=None):
    """One decode tick of ``models.hybrid``'s paged forward over global
    layers whose widths the kernel takes (8 query heads over one K/V
    head, K of 136 in a part of 256, V of 128, pages of 16), 3 slots of
    which one is inactive."""
    import functools

    from ddl_tpu.models import hybrid
    from ddl_tpu.ops import paged_attention
    from ddl_tpu.serve.cache import hybrid_cache

    spec = hybrid.HybridSpec(
        vocab=32, d_model=32, num_heads=8, head_dim=136, v_head_dim=128,
        kv_heads_global=1, rotary_dim=8, d_ff=32,
        layer_kinds=(hybrid.GLOBAL,) * 2, ffn_kinds=(hybrid.DENSE,) * 2)
    params = hybrid.init_hybrid_params(jax.random.PRNGKey(3), spec)
    cache = hybrid_cache(spec, 12, 0, 16, jnp.float32)
    assert cache.k[0].shape == (12, 1, 16, 384) and cache.v[0] is None
    pools = {i: (paged_attention.grouped_rows(
        jax.random.normal(jax.random.PRNGKey(i), (12, 1, 16, 136)),
        jax.random.normal(jax.random.PRNGKey(9 + i), (12, 1, 16, 128))), None)
        for i in range(2)}
    table = jnp.asarray([[3, 5, 7, -1], [1, -1, -1, -1], [9, 11, -1, -1]])
    active = jnp.asarray([True, True, False])
    positions = jnp.where(active, jnp.asarray([40, 9, 20]), -1)
    if monkeypatch is not None:
        monkeypatch.setattr(
            paged_attention, "grouped_decode_attention", functools.partial(
                paged_attention.grouped_decode_attention, interpret=True))

    def forward(params, pools, tokens):
        return hybrid.apply_hybrid_paged(
            params, pools, tokens, spec, page_size=16, g_table=table,
            w_table=None, positions=positions[:, None],
            real=active[:, None], last=positions, platform=platform)

    return forward, (params, pools, jnp.asarray([[1], [2], [3]]))


def test_hybrid_decode_reads_global_pages_in_place_on_a_tpu(monkeypatch):
    """The rule ``PagedMixer._global`` chooses by: on a TPU, at widths the
    kernel takes, a global layer's decode is one
    ``grouped_decode_attention`` a layer (one traced kernel) and no
    gathered view; the CPU gathers from the same pool. Both give the same
    rows, pools and hidden state."""
    forward, args = _global_decode("tpu", monkeypatch)
    trace = str(jax.make_jaxpr(forward)(*args))
    assert len(re.findall(r"jit\[\s*name=grouped_decode_attention",
                          trace)) == 2
    assert trace.count("pallas_call[") == 1
    assert "f32[3,64,384]" not in trace        # the gathered view
    plain, _ = _global_decode("cpu")
    assert "f32[3,64,384]" in str(jax.make_jaxpr(plain)(*args))
    assert "grouped_decode_attention" not in str(
        jax.make_jaxpr(plain)(*args))
    h, pools, _ = forward(*args)
    want_h, want_pools, _ = plain(*args)
    live = np.asarray([0, 1])
    np.testing.assert_allclose(np.asarray(h)[live], np.asarray(want_h)[live],
                               atol=2e-5)
    np.testing.assert_array_equal(pools[0][0], want_pools[0][0])
    np.testing.assert_allclose(pools[1][0], want_pools[1][0], atol=2e-5)
    # the tick's own rows went where the table says, the head before the row
    assert float(jnp.abs(pools[0][0][7, 0, 40 % 16, :136]).max()) > 0
    np.testing.assert_array_equal(pools[0][0][9], args[1][0][0][9])


@pytest.mark.parametrize("why,t,platform,head_dim", [
    ("prefill_block", 16, "tpu", 136),
    ("widths_under_a_tile", 1, "tpu", 24),
    ("platform_cpu", 1, "cpu", 136),
])
def test_hybrid_global_layers_gather_where_the_kernel_does_not_fit(
        why, t, platform, head_dim):
    """A prefill block (``base`` given), heads under a lane tile or a CPU:
    short of any one the global layers gather their views."""
    from ddl_tpu.models import hybrid
    from ddl_tpu.serve.cache import hybrid_cache

    spec = hybrid.HybridSpec(
        vocab=32, d_model=32, num_heads=8, head_dim=head_dim,
        v_head_dim=128, kv_heads_global=1, rotary_dim=8, d_ff=32,
        layer_kinds=(hybrid.GLOBAL,), ffn_kinds=(hybrid.DENSE,))
    params = jax.eval_shape(
        lambda: hybrid.init_hybrid_params(jax.random.PRNGKey(0), spec))
    cache = jax.eval_shape(lambda: hybrid_cache(spec, 12, 0, 16, jnp.float32))
    b = 1 if t > 1 else 3
    positions = jnp.arange(t, dtype=jnp.int32)[None] + jnp.zeros(
        (b, 1), jnp.int32)

    def forward(params, pool, tokens):
        return hybrid.apply_hybrid_paged(
            params, {0: (pool, None)}, tokens, spec, page_size=16,
            g_table=jnp.zeros((b, 4), jnp.int32), w_table=None,
            positions=positions, real=positions >= 0,
            last=positions[:, -1], base=jnp.int32(0) if t > 1 else None,
            platform=platform)[0]

    trace = str(jax.make_jaxpr(forward)(
        params, cache.k[0], jax.ShapeDtypeStruct((b, t), jnp.int32)))
    assert "pallas_call" not in trace
    assert "grouped_decode_attention" not in trace
