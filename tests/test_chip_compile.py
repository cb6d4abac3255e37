"""What can be known about the chip without the chip (ISSUE 21).

- The main path's KERNELS compiled at real widths for a described (not
  attached) TPU v5e: the fused Adam kernel at the full flat vector (not a
  multiple of 128 — the pad path) and at the lane-aligned quarter shard,
  flash attention forward + backward at the ``chip_smoke.py`` LM shape,
  at T = 4096, at the benchmark's training shape (8 rows of 2048, 16
  heads of 64: the blocks ``flash_block_sizes`` picks there must fit VMEM),
  at two lengths no wide block divides (384, 1152) and at heads of 512,
  T = 8192, where the unhalved blocks are refused; the config gate
  that keeps a sequence the kernel would refuse from ever reaching it;
  and the paged decode-attention kernel at the serving cell's shape and
  at an fp32 pool under a bucket no page group divides (ISSUE 31), and
  the latent pool's at its cell's shape (ISSUE 32), and the grouped
  pool's at its cell's shape (ISSUE 35).
  One to four seconds each; skipped where the TPU compiler cannot
  describe the topology. Beside them the dense paged forward at the
  serving cell's widths, decode and prefill: no layer's pool is copied
  out of the stacked pool or back (ISSUE 29); and the second family's
  decode over latent layers at its cell's widths: no pool is copied, no
  view gathered (ISSUE 32); and its sparse and linear layers' decode and
  prefill at their cell's widths: neither the head-major pools nor the
  state group is copied (ISSUE 34); and its global layers' decode at
  their cell's widths: no pool copied, no view gathered or re-laid out
  (ISSUE 35). The persistent compile
  cache is off for the whole suite (conftest) — a described-device
  compile can be written to it but never read back without a chip.
  Whole-step compiles
  take ten seconds and more each, so they are not tier-1: the CNN span
  and the four-chip ZeRO-1 step are here under ``-m slow`` (they take a
  mesh, so a described one can be handed to them), and every product
  program at full width is compiled by ``chip_smoke.py`` on the chip.
- The device gates around the chip: peak tables that know the v5e's
  ``device_kind`` and refuse an unknown accelerator, the compile cache's
  placement rule, and ``chip_smoke.py`` refusing to report from a CPU.
"""

import dataclasses
import json
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a described (not attached) v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_host):
    """Sharding on one chip of that host."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


def _compile_adam(chip, n):
    from ddl_tpu.ops.pallas_adam import adam_flat_fused

    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    return jax.jit(adam_flat_fused).lower(vec, vec, vec, vec, lr).compile()


def _compile_flash(chip, batch, seq_len, heads=8, head_dim=64):
    from ddl_tpu.ops.attention import flash_attention_bthd

    def loss(q, k, v):
        out = flash_attention_bthd(q, k, v, causal=True, platform="tpu")
        return (out.astype(jnp.float32) ** 2).sum()

    qkv = jax.ShapeDtypeStruct((batch, seq_len, heads, head_dim),
                               jnp.bfloat16, sharding=chip)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile()


def _compile_paged_decode(chip, dtype, slots, bucket, pool=(16, 512, 64),
                          heads=8, head_dim=256):
    from ddl_tpu.ops.paged_attention import (kernel_accepts,
                                             paged_decode_attention)

    assert kernel_accepts(heads, head_dim, pool[2])
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    kv = on(pool + (heads, head_dim), dtype)
    return jax.jit(paged_decode_attention).lower(
        on((slots, heads, head_dim), dtype), kv, kv,
        on(pool[1:], jnp.int32), on((slots, bucket), jnp.int32),
        on((slots,), jnp.int32), on((), jnp.int32)).compile()


def _compile_latent_decode(chip, slots, bucket, pool=(8192, 64, 640),
                           heads=64, v_width=512):
    from ddl_tpu.ops.paged_attention import (latent_decode_attention,
                                             latent_kernel_accepts)

    assert latent_kernel_accepts(heads, pool[2], v_width, pool[1])
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    return jax.jit(lambda *a: latent_decode_attention(
        *a, scale=0.13, v_width=v_width)).lower(
        on((slots, heads, pool[2]), jnp.bfloat16), on(pool, jnp.bfloat16),
        on((slots, bucket), jnp.int32), on((slots,), jnp.int32)).compile()


def _compile_grouped_decode(chip, slots, bucket, pool=(4096, 4, 64, 384),
                            group_heads=16, head_dim=192, v_head_dim=128):
    from ddl_tpu.ops.paged_attention import (grouped_decode_attention,
                                             grouped_kernel_accepts)

    assert grouped_kernel_accepts(group_heads, head_dim, v_head_dim, pool[2])
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    return jax.jit(lambda *a: grouped_decode_attention(
        *a, v_head_dim=v_head_dim)).lower(
        on((slots, pool[1], group_heads, head_dim), jnp.bfloat16),
        on(pool, jnp.bfloat16), on((slots, bucket), jnp.int32),
        on((slots,), jnp.int32)).compile()


@pytest.mark.parametrize("case", [
    "adam_full_vector", "adam_quarter_shard", "flash_lm_shape",
    "flash_t4096", "flash_refused_below_block", "flash_cell_shape",
    "flash_t384", "flash_t1152", "flash_head_dim_512",
    "paged_decode_cell_shape", "paged_decode_fp32_odd_bucket",
    "latent_decode_cell_shape", "grouped_decode_cell_shape",
])
def test_kernels_compile_for_v5e(v5e, case):
    if case == "flash_refused_below_block":
        from ddl_tpu.strategies.seq import SeqConfig

        cfg = SeqConfig(scheme="full", attn_impl="flash")
        cfg.validate_topology(seq_len=1024, platform="tpu")
        cfg.validate_topology(seq_len=64, platform="cpu")  # reference twin
        for bad in (64, 192):  # under one block; not whole blocks
            with pytest.raises(ValueError, match="multiple of 128"):
                cfg.validate_topology(seq_len=bad, platform="tpu")
        # ...and the kernel itself does refuse what the gate refuses.
        with pytest.raises(ValueError, match="block_q"):
            _compile_flash(v5e, 8, 64)
        return
    compiled = {
        "adam_full_vector": lambda: _compile_adam(v5e, 2_656_010),
        "adam_quarter_shard": lambda: _compile_adam(v5e, 664_064),
        "flash_lm_shape": lambda: _compile_flash(v5e, 8, 1024),
        "flash_t4096": lambda: _compile_flash(v5e, 2, 4096),
        "flash_cell_shape": lambda: _compile_flash(v5e, 8, 2048, heads=16),
        "flash_t384": lambda: _compile_flash(v5e, 2, 384),
        "flash_t1152": lambda: _compile_flash(v5e, 2, 1152),
        "flash_head_dim_512": lambda: _compile_flash(v5e, 1, 8192, heads=2,
                                                     head_dim=512),
        # The serving cell's widest bucket, and one page a grid step
        # (a bucket no group divides) over an fp32 pool of 16-row pages
        # and 16 heads of 128.
        "paged_decode_cell_shape": lambda: _compile_paged_decode(
            v5e, jnp.bfloat16, 32, 36),
        "paged_decode_fp32_odd_bucket": lambda: _compile_paged_decode(
            v5e, jnp.float32, 4, 5, pool=(2, 16, 16), heads=16,
            head_dim=128),
        # 64 heads of 640 over 8,192 pages of 64 rows, the widest bucket.
        "latent_decode_cell_shape": lambda: _compile_latent_decode(
            v5e, 64, 272),
        # 4 x 16 heads of 192 / 128 over 4,096 pages of 64 rows of 384,
        # the widest bucket.
        "grouped_decode_cell_shape": lambda: _compile_grouped_decode(
            v5e, 64, 144),
    }[case]()
    # The kernel is in the program, not a reference twin.
    assert "tpu_custom_call" in compiled.as_text()
    if "_decode_" in case:
        # The kernel's view of the stack is the stack's own bytes.
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def _shapes_on(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


_HLO_INSTR = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = ([a-z0-9]+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def _pool_sized_copies(hlo: str, at_least: int) -> list[str]:
    """Instructions of the ENTRY computation of an optimised HLO module
    that copy ``at_least`` bytes or more: a ``slice``, a
    ``dynamic-update-slice`` or a ``copy`` (their async halves too),
    alone or as the root of the fusion called."""
    from ddl_tpu.obs.comms import _DTYPE_BYTES

    roots, entry, name = {}, [], None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            continue
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        if name == "ENTRY":
            entry.append((m, line))
        elif m.group(1):
            roots[name] = m.group(5)
    found = []
    for m, line in entry:
        _, instr, dtype, dims, opcode = m.groups()
        size = _DTYPE_BYTES.get(dtype, 4) * math.prod(
            int(d) for d in dims.split(",") if d)
        if opcode == "fusion":
            opcode = roots[re.search(r"calls=%?([\w.\-]+)", line).group(1)]
        kind = opcode.removesuffix("-start").removesuffix("-done")
        if size >= at_least and kind in ("slice", "dynamic-update-slice",
                                         "copy"):
            found.append(f"{instr}: {opcode} of {dtype}[{dims}]")
    return found


def _weight_casts(hlo: str, spec) -> list[str]:
    """Instructions of an optimised HLO module, in any computation, that
    read a value of a dense weight's shape from a parameter and convert
    it to bf16: a weight cast to the compute dtype inside the program.
    The one-row head's product, rewritten as a multiply and a reduction
    in one fusion, widens its bf16 weight to fp32 there and narrows the
    product back: neither is a cast of a weight to bf16."""
    e, f, v = spec.d_model, spec.d_ff, spec.vocab
    dims = "|".join(f"{a},{b}" for a, b in (
        (v, e), (e, v), (e, e), (e, f), (f, e)))
    cast = re.compile(rf"= bf16\[({dims})\]\S* convert\(%?param")
    return [line.strip()[:100] for line in hlo.splitlines()
            if cast.search(line)]


@pytest.mark.parametrize("program", ["decode_32x1_p32", "prefill_1x512",
                                     "decode_32x1_p32_int8",
                                     "prefill_1x512_one_row",
                                     "decode_32x1_p32_bf16_weights",
                                     "prefill_1x512_one_row_bf16_weights"])
def test_paged_forward_writes_the_stacked_pool_in_place(v5e, program):
    """``apply_lm_paged`` at the widths of ``serve-1b-closed32`` (8 heads
    of 256, a bf16 pool of 512 pages x 64 rows, fp32 weights computed in
    bf16; 2 layers), pools donated, compiled for the described v5e: no
    layer's pool (134 MB; 67 MB of payload in the int8 pool) is copied
    out of the stacked array or back into it. Before ISSUE 29 each layer
    cost a ``slice`` and a ``dynamic-update-slice`` fusion of that size
    for K and for V, 42% of the cell's busy time on the chip, and the
    bf16 decode held 794 MB of temporaries.

    The bf16 decode reads its pages in place (ISSUE 31): one
    ``paged_decode_attention`` kernel a layer and no gathered view
    ``[32, 32 x 64, 8, 256]`` (268 MB each for K and V a layer, 84% of
    the decode program before it; under 400 MB of temporaries then).
    The kernel's view of the stack, ``[L * pages, 64 x 8, 256]``, has
    to be the same bytes: a view the chip's tiled layout does not share
    (``[..., 64, 8 x 256]``) would show here as a copy of the whole
    pool. Prefill (512 queries a slot) and the int8 pool keep the
    gathered path, and no kernel.

    The prefill the scheduler runs applies the head to its last real
    row alone (ISSUE 33, ``last_row``): no ``[512, 50304]`` value, which
    the all-rows form holds in fp32 (103 MB; 412 MB at the 2048 bucket)
    for a host that dropped it.

    Handed fp32 weights, each program casts every matrix to bf16 inside
    it, and the decode program holds the whole ``[50304, 2048]``
    embedding cast to look up 32 rows: 206 MB of its temporaries. The
    dense engine holds its weights in the compute dtype
    (``InferenceEngine._place``): handed them so (``_bf16_weights``), a
    program casts no weight, and the decode holds under 60 MB."""
    from ddl_tpu.models.transformer import (LMSpec, apply_lm_paged,
                                            init_lm_params)

    spec = LMSpec(vocab=50304, d_model=2048, num_heads=8, num_layers=2,
                  d_ff=8192)
    pages, page, slots = 512, 64, 32
    b, t, tp = (slots, 1, 32) if program.startswith("decode") else (
        1, 512, 36)
    int8 = program.endswith("int8")
    bf16_weights = program.endswith("bf16_weights")
    program = program.removesuffix("_bf16_weights")
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    stack = (spec.num_layers, pages, page, spec.num_heads)
    pool = on(stack + (spec.head_dim,), jnp.int8 if int8 else jnp.bfloat16)
    scales = (on(stack, jnp.float32),) * 2 if int8 else (None, None)
    layer_pool_bytes = pool.dtype.itemsize * math.prod(pool.shape[1:])
    assert layer_pool_bytes == (67_108_864 if int8 else 134_217_728)
    weights = jax.eval_shape(
        lambda: init_lm_params(jax.random.PRNGKey(0), spec))
    if bf16_weights:
        weights = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), weights)

    one_row = program.endswith("one_row")

    def forward(params, pool_k, pool_v, pool_pos, k_scale, v_scale, tokens,
                table, positions, flat_rows):
        return apply_lm_paged(
            params, tokens, pool_k, pool_v, pool_pos, table, spec,
            positions=positions, flat_rows=flat_rows,
            compute_dtype=jnp.bfloat16, pool_k_scale=k_scale,
            pool_v_scale=v_scale, platform="tpu",
            last_row=positions[0, 0] if one_row else None)

    compiled = jax.jit(forward, donate_argnums=(1, 2, 3, 4, 5)).lower(
        _shapes_on(weights, v5e),
        pool, pool, on((pages, page), jnp.int32), *scales,
        on((b, t), jnp.int32), on((b, tp), jnp.int32), on((b, t), jnp.int32),
        on((b, t), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert _pool_sized_copies(hlo, layer_pool_bytes) == []
    assert (_weight_casts(hlo, spec) == []) == bf16_weights
    if program == "decode_32x1_p32":
        assert sum("custom-call(" in line and "paged_decode_attention" in line
                   for line in hlo.splitlines()) == spec.num_layers
        assert f"[{slots},{tp * page},{spec.num_heads},{spec.head_dim}]" \
            not in hlo
        assert compiled.memory_analysis().temp_size_in_bytes < (
            60e6 if bf16_weights else 250e6)
    else:
        assert "paged_decode_attention" not in hlo
    if program.startswith("prefill"):
        assert (f"f32[1,{t},{spec.vocab}]" in hlo) != one_row
        assert (f"[{t},{spec.vocab}]" in hlo) != one_row


def test_latent_decode_reads_its_pool_in_place(v5e):
    """A decode tick of ``models.hybrid`` over latent layers at the
    widths of ``serve-k2-closed64-long`` (64 heads, rows of 576 in pools
    of 8,192 pages x 64 rows x 640, bf16; the dense layer and one routed
    layer; 64 slots at the widest bucket, 272 pages), pools donated,
    compiled for the described v5e: no pool (671 MB) is copied, each
    layer's attention is one ``latent_decode_attention`` kernel, no view
    ``[64, 17408, 640]`` (1.43 GB a layer) is gathered, and the program
    holds under 100 MB of temporaries (51 MB at five layers). A pool of
    576-value rows, 4.5 lane tiles, is kept by the chip with the pages
    minor-most and was copied in and out, whole, for every layer
    (``serve.cache.latent_pool_width``)."""
    import dataclasses

    from ddl_tpu.models import hybrid
    from ddl_tpu.serve.cache import latent_pool_width

    spec = dataclasses.replace(
        hybrid.NAMED_SPECS["kimi-k2-ep32"],
        layer_kinds=(hybrid.LATENT,) * 2,
        ffn_kinds=(hybrid.DENSE, hybrid.MOE))
    pages, page, slots, bucket = 8192, 64, 64, 272
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    params = jax.tree.map(
        lambda a: on(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: hybrid.init_hybrid_params(jax.random.PRNGKey(0), spec)))
    width = latent_pool_width(spec)
    assert width == 640
    pools = {i: (on((pages, page, width), jnp.bfloat16), None)
             for i in range(spec.num_layers)}

    def run(params, pools, last_tokens, lengths, active, g_table):
        positions = jnp.where(active, lengths, -1)
        h, pools, counts = hybrid.apply_hybrid_paged(
            params, pools, last_tokens[:, None], spec, page_size=page,
            g_table=g_table, w_table=None, positions=positions[:, None],
            real=active[:, None], last=positions,
            compute_dtype=jnp.bfloat16, platform="tpu")
        return hybrid.head_logits(params, h[:, 0]), pools, counts

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, pools, on((slots,), jnp.int32), on((slots,), jnp.int32),
        on((slots,), jnp.bool_), on((slots, bucket), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert _pool_sized_copies(hlo, pages * page * width) == []
    assert sum("custom-call(" in line and "latent_decode_attention" in line
               for line in hlo.splitlines()) == spec.num_layers
    assert f"[{slots},{bucket * page},{width}]" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("program", ["decode_p144", "decode_p8",
                                     "prefill_b1024"])
def test_global_layers_read_their_pool_in_place(v5e, program):
    """``models.hybrid`` over a global and a window layer at the widths of
    ``serve-mimo-closed64-mixed`` (64 query heads of 192 over 4 K/V heads
    with V heads of 128; a global pool of 4,096 pages ``[4, 64, 384]``
    bf16, the head before the row and a row ``[k 192 | 64 zeros | v
    128]``; 64 slots), pools donated, compiled for the described v5e: no
    pool (805 MB) is copied by the scatter that writes a tick's rows or a
    block's, a decode tick's global attention is one
    ``grouped_decode_attention`` kernel, and no view of the slots' tables
    exists in any layout: neither the rows gathered (``[64, 9216, ...]``:
    1.51 GB a layer at the widest bucket, re-laid out heads-major every
    tick before ISSUE 35, a third of the cell's busy time as ``reshape``)
    nor a ``[64, 144, 4, 64, 384]`` gather. A prefill block gathers ONE
    slot's pages (28 MB) and runs no kernel."""
    from ddl_tpu.models import hybrid
    from ddl_tpu.serve.cache import hybrid_cache, ring_columns

    spec = dataclasses.replace(
        hybrid.NAMED_SPECS["mimo-v2-flash-ep16"],
        layer_kinds=(hybrid.GLOBAL, hybrid.WINDOW),
        ffn_kinds=(hybrid.DENSE, hybrid.DENSE))
    pages, page, slots = 4096, 64, 64
    ring = ring_columns(spec.window, page)
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    params = jax.tree.map(
        lambda a: on(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: hybrid.init_hybrid_params(jax.random.PRNGKey(0), spec)))
    cache = jax.tree.map(lambda a: on(a.shape, a.dtype), jax.eval_shape(
        lambda: hybrid_cache(spec, pages, slots * ring, page, jnp.bfloat16)))
    assert cache.k[0].shape == (pages, 4, page, 384) and cache.v[0] is None
    assert cache.k[1].shape == (slots * ring, page, 8 * 192)
    pools = {0: (cache.k[0], None), 1: (cache.k[1], cache.v[1])}

    def forward(params, pools, tokens, **kw):
        h, pools, _ = hybrid.apply_hybrid_paged(
            params, pools, tokens, spec, page_size=page,
            compute_dtype=jnp.bfloat16, platform="tpu", **kw)
        return h, pools

    i32 = on((), jnp.int32)
    if program.startswith("decode"):
        bucket = int(program.split("_p")[1])

        def run(params, pools, last_tokens, lengths, active, g_table,
                w_table):
            positions = jnp.where(active, lengths, -1)
            h, pools = forward(
                params, pools, last_tokens[:, None], g_table=g_table,
                w_table=w_table, positions=positions[:, None],
                real=active[:, None], last=positions)
            return hybrid.head_logits(params, h[:, 0]), pools

        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, bucket), jnp.int32),
                on((slots, ring), jnp.int32))
    else:
        bucket, t = 144, 1024

        def run(params, pools, tokens, length, base, g_table, w_table):
            at = jnp.arange(t, dtype=jnp.int32)
            real = (at < length)[None, :]
            h, pools = forward(
                params, pools, tokens, g_table=g_table, w_table=w_table,
                positions=jnp.where(real, base + at, -1), real=real,
                last=(base + length - 1)[None], base=base)
            return h[0, -1], pools

        args = (on((1, t), jnp.int32), i32, i32, on((1, bucket), jnp.int32),
                on((1, ring), jnp.int32))
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, pools, *args).compile()
    hlo = compiled.as_text()
    assert _pool_sized_copies(hlo, pages * 4 * page * 384 * 2) == []
    kernels = sum("tpu_custom_call" in line
                  and "grouped_decode_attention" in line
                  for line in hlo.splitlines())
    assert kernels == (1 if program.startswith("decode") else 0)
    for view in (f"[{slots},{bucket * page},", f"[{slots},{bucket},4,",
                 f"[{slots},4,{bucket * page},",
                 f"[{slots},{bucket * 4 * page},"):
        assert view not in hlo, view
    # A tick's temporaries are the window layer's ring views (63 MB) and
    # the matmuls' (3.0 GB of global views a layer before ISSUE 35).
    limit = 400e6 if program.startswith("prefill") else 150e6
    assert compiled.memory_analysis().temp_size_in_bytes < limit


@pytest.mark.parametrize("program", ["decode_p560", "decode_p64",
                                     "prefill_b2048"])
def test_sparse_and_linear_layers_keep_their_caches_in_place(v5e, program):
    """``models.hybrid`` over a sparse and a linear layer at the widths of
    ``serve-sala-closed64-32k`` (32 query heads of 128 over 2 K/V heads,
    a pool of 24,576 pages ``[2, 128, 128]`` bf16, the head before the
    row and a head's K rows before its V rows, 64 slots of ``[32, 128, 128]`` fp32 state), caches donated,
    compiled for the described v5e: no pool (1.61 GB) and no layer's state
    (134 MB) is copied, whatever the program reads or writes of them; a
    decode tick's sparse attention is one ``sparse_decode_attention``
    kernel and no view ``[64, 2, 35840, 128]`` is gathered (the widest
    bucket, past ``dense_len``: the selector runs; the 64-page bucket:
    every page listed); a prefill chunk runs no kernel."""
    from ddl_tpu.models import hybrid
    from ddl_tpu.serve.cache import HybridKVCache, hybrid_cache

    spec = dataclasses.replace(
        hybrid.NAMED_SPECS["minicpm-sala-l8"],
        layer_kinds=(hybrid.SPARSE, hybrid.LINEAR),
        ffn_kinds=(hybrid.DENSE,) * 2)
    pages, page, slots = 24576, 64, 64
    on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    params = jax.tree.map(
        lambda a: on(a.shape, jnp.bfloat16), jax.eval_shape(
            lambda: hybrid.init_hybrid_params(jax.random.PRNGKey(0), spec)))
    cache = jax.tree.map(lambda a: on(a.shape, a.dtype), jax.eval_shape(
        lambda: hybrid_cache(spec, pages, 0, page, jnp.bfloat16, slots)))
    assert cache.k[0].shape == (pages, 2, 2 * page, 128)
    assert cache.v[0] is None
    assert cache.extra[0].shape == (pages * 2 * 4, 128)
    assert cache.extra[1].shape == (slots, 32, 128, 128)
    assert cache.extra[1].dtype == jnp.float32 and cache.k[1] is None

    def forward(params, cache, tokens, **kw):
        pools = {i: kept if kept[2] is not None else kept[:2] for i, kept
                 in enumerate(zip(cache.k, cache.v, cache.extra))}
        h, pools, _ = hybrid.apply_hybrid_paged(
            params, pools, tokens, spec, page_size=page, w_table=None,
            compute_dtype=jnp.bfloat16, platform="tpu", **kw)
        both = range(2)
        return h, HybridKVCache(
            k=tuple(pools[i][0] for i in both),
            v=tuple(pools[i][1] for i in both),
            extra=tuple(pools[i][2] for i in both))

    i32 = on((), jnp.int32)
    if program.startswith("decode"):
        bucket = int(program.split("_p")[1])

        def run(params, cache, last_tokens, lengths, active, g_table):
            positions = jnp.where(active, lengths, -1)
            h, cache = forward(
                params, cache, last_tokens[:, None], g_table=g_table,
                positions=positions[:, None], real=active[:, None],
                last=positions)
            return hybrid.head_logits(params, h[:, 0]), cache

        args = (on((slots,), jnp.int32), on((slots,), jnp.int32),
                on((slots,), jnp.bool_), on((slots, bucket), jnp.int32))
    else:
        bucket = 2048

        def run(params, cache, tokens, length, base, g_table, slot):
            t = jnp.arange(bucket, dtype=jnp.int32)
            real = (t < length)[None, :]
            h, cache = forward(
                params, cache, tokens, g_table=g_table,
                positions=jnp.where(real, base + t, -1), real=real,
                last=(base + length - 1)[None], base=base, slot=slot)
            return h[0, -1], cache

        args = (on((1, bucket), jnp.int32), i32, i32,
                on((1, 560), jnp.int32), i32)
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    # A prefill updates ONE slot's 2 MiB of the state where it lies (a
    # dynamic-update-slice whose result names the whole array).
    state, pool = slots * 32 * 128 * 128 * 4, pages * 2 * 2 * page * 128 * 2
    assert _pool_sized_copies(
        hlo, state if program.startswith("decode") else pool) == []
    kernels = sum("tpu_custom_call" in line
                  and "sparse_decode_attention" in line
                  for line in hlo.splitlines())
    assert kernels == (1 if program.startswith("decode") else 0)
    assert "[64,2,35840,128]" not in hlo and "[64,2,4096,128]" not in hlo
    limit = 700e6 if program.startswith("prefill") else 200e6
    assert compiled.memory_analysis().temp_size_in_bytes < limit


@pytest.mark.slow
@pytest.mark.parametrize("program", ["cnn_span_bf16", "zero1_flat_4chip"])
def test_cnn_step_programs_compile_for_v5e(v5e_host, program, monkeypatch):
    """The full-width CNN programs, compiled for the described host: the
    single-chip span (``make_epoch_chunk``, bf16, batch 100) and the
    four-chip ZeRO-1 flat span (``make_sync_epoch``). ``steps_scan`` asks
    ``jax.default_backend()``, which is the CPU here, so the test steers
    it to the rolled scan the chip runs."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ddl_tpu.models import cnn
    from ddl_tpu.obs.comms import collective_ops
    from ddl_tpu.ops import adam_init
    from ddl_tpu.parallel.mesh import DP_AXIS
    from ddl_tpu.strategies.sync import (
        ShardedAdam, make_sync_epoch, resolve_layout)
    from ddl_tpu.train.config import TrainConfig
    from ddl_tpu.train.trainer import make_epoch_chunk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    k = 10
    if program == "cnn_span_bf16":
        chip = NamedSharding(Mesh(np.asarray(v5e_host[:1]), (DP_AXIS,)), P())
        cfg = TrainConfig(batch_size=100, compute_dtype="bfloat16")
        on = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
        compiled = make_epoch_chunk(cfg, k).lower(
            _shapes_on(params, chip),
            _shapes_on(jax.eval_shape(adam_init, params), chip),
            on((k, 100, 784), jnp.bfloat16), on((k, 100, 10), jnp.float32),
            *_shapes_on((i32, i32, key), chip)).compile()
        assert collective_ops(compiled.as_text()) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
        return
    W = 4
    mesh = Mesh(np.asarray(v5e_host), (DP_AXIS,))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P(DP_AXIS))
    shapes = cnn.param_shapes(params)
    sizes = {n: int(np.prod(s)) if s else 1 for n, s in shapes.items()}
    cfg = TrainConfig(batch_size=100 * W, num_workers=W, num_ps=W,
                      layout="flat", keep_prob=1.0)
    layout = resolve_layout(cfg, W, sizes)
    flat = jax.ShapeDtypeStruct((W * layout.max_shard,), jnp.float32,
                                sharding=split)
    lowered = make_sync_epoch(cfg, mesh, layout, shapes, k).lower(
        _shapes_on(params, rep),
        ShardedAdam(step=_shapes_on(i32, rep), m=flat, v=flat),
        jax.ShapeDtypeStruct((W, k, 100, 784), jnp.float32, sharding=split),
        jax.ShapeDtypeStruct((W, k, 100, 10), jnp.float32, sharding=split),
        *_shapes_on((i32, i32, key), rep))
    ops = collective_ops(lowered.compile().as_text())
    kinds = {op["op"] for op in ops}
    # The program asks for a reduce-scatter; on this topology the TPU
    # compiler answers with an all-reduce of the whole flat vector and a
    # slice (PERF.md, PR 21). Either way the gradients are reduced once
    # and the parameters gathered once.
    assert "reduce_scatter" in lowered.as_text()
    assert "all-gather" in kinds
    assert "reduce-scatter" in kinds or any(
        op["op"] == "all-reduce" and op["max_elems"] >= W * layout.max_shard
        for op in ops)


@pytest.mark.parametrize("table", ["flops", "ici"])
def test_peak_tables_know_v5e_and_refuse_unknown_accelerators(table):
    """A v5e reports device_kind "TPU v5 lite"; an accelerator in neither
    table raises instead of borrowing the CPU nominal."""
    from ddl_tpu.obs import comms, cost

    lookup, want = {
        "flops": (cost.peak_flops_per_device, 197e12),
        "ici": (comms.ici_bw_per_device, 2.0e11),
    }[table]
    v5e_dev = types.SimpleNamespace(device_kind="TPU v5 lite",
                                    platform="tpu")
    assert lookup(v5e_dev) == want
    unknown = types.SimpleNamespace(device_kind="TPU v9 mega",
                                    platform="tpu")
    with pytest.raises(cost.UnknownDeviceKind, match="TPU v9 mega".lower()):
        lookup(unknown)
    assert lookup(jax.devices()[0]) > 0  # the CPU nominal stays defined


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placement(env_set, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX (no config
    write); otherwise the directory is <checkout>/.jax_cache, the same
    on every call."""
    from ddl_tpu.utils import compile_cache

    writes = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: writes.__setitem__(k, v))
    if env_set:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in writes
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        first, second = compile_cache.enable(), compile_cache.enable()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert writes["jax_compilation_cache_dir"] == first


def test_chip_smoke_refuses_to_report_from_cpu(capsys, monkeypatch):
    """The platform gate alone (phases stubbed out): on the CPU backend
    chip_smoke.py exits non-zero and never prints the result line — with
    or without --rehearse."""
    import chip_smoke as smoke  # repo root is on sys.path, as for bench

    ran = []
    stub = (("stub", lambda sizes, seed: ran.append(sizes) or {}),)
    monkeypatch.setattr(smoke, "ONE_CHIP", stub)
    monkeypatch.setattr(smoke, "FOUR_CHIPS", stub)
    from ddl_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "unused")
    # No process-wide JAX monitoring listeners left behind by a test.
    monkeypatch.setattr(smoke, "CompileMeter", lambda: types.SimpleNamespace(
        snapshot=lambda: (0.0, 0, 0)))

    assert smoke.main([]) != 0 and not ran  # stops before any phase
    assert smoke.main(["--rehearse"]) != 0 and ran == [smoke.TINY]
    assert smoke.main(["--chips", "4", "--rehearse"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        assert json.loads(line)["phase"] in ("setup", "stub")
