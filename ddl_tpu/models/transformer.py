"""Decoder-only transformer LM — the long-context model family.

The reference has no attention and no sequence axis at all (fixed
784-pixel image inputs, mnist_sync/model/model.py:18-19; SURVEY.md §5
records sequence parallelism as owed nothing for parity). This family
exists so the sequence-parallel machinery in ``ddl_tpu.parallel.ring``
(ring attention over ``ppermute``, Ulysses over ``all_to_all``) is a
product surface rather than an op library: ``ddl_tpu.strategies.seq``
trains this model with the sequence dimension sharded across the mesh.

TPU-first design decisions:

- **Pluggable attention**: :func:`apply_lm` takes ``attn_fn(q, k, v)``,
  so the SAME model code runs single-device (``ring.full_attention``)
  or per-shard inside ``shard_map`` (``ring.ring_attention_shard`` /
  ``ring.ulysses_attention_shard``). The model never knows whether its
  sequence axis is whole or a shard.
- **RoPE, not a position table**: positions enter as rotations of q/k
  computed from ABSOLUTE positions (``pos_offset`` + local arange), so a
  shard holding positions ``[o, o + T/P)`` produces exactly the rotations
  the full sequence would — K/V blocks travelling around the ring carry
  their positions baked in. A learned position table would need the same
  offset plumbing plus a vocab-style lookup; RoPE needs neither state nor
  gather.
- **Pre-LN blocks** (LN -> attn -> residual, LN -> MLP -> residual):
  everything except attention is position-local, so sequence sharding is
  transparent; the only cross-shard ops in the whole network are inside
  ``attn_fn``.
- Matmul-shaped throughout (QKV/O projections, MLP, logits) — the MXU
  path; ``compute_dtype=jnp.bfloat16`` casts weights/activations while
  keeping logits/loss fp32, same contract as ``models.cnn``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

Params = Mapping[str, Any]
AttnFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class LMSpec:
    """Architecture of one family member. ``head_dim`` must be even
    (RoPE rotates dimension pairs)."""

    vocab: int = 256
    d_model: int = 256
    num_heads: int = 8
    num_layers: int = 4
    d_ff: int = 1024
    rope_base: float = 10000.0

    @property
    def head_dim(self) -> int:
        if self.d_model % self.num_heads:
            raise ValueError(
                f"d_model {self.d_model} not divisible by "
                f"{self.num_heads} heads"
            )
        return self.d_model // self.num_heads

    def num_params(self) -> int:
        e, f, v = self.d_model, self.d_ff, self.vocab
        per_block = 4 * e * e + 2 * e * f + f + e + 4 * e
        return v * e + self.num_layers * per_block + 2 * e + e * v


# Test/dryrun-sized member of the family (same structure, ~1/100 the FLOPs).
TINY_SPEC = LMSpec(vocab=32, d_model=32, num_heads=2, num_layers=2, d_ff=64)


def init_lm_params(
    key: jax.Array, spec: LMSpec = LMSpec(), dtype=jnp.float32
) -> dict[str, Any]:
    """Glorot-uniform projections (matching ``cnn.init_params``' TF1
    default), unit LN gains, zero biases, output head included (untied)."""

    def glorot(k, shape):
        limit = math.sqrt(6.0 / (shape[0] + shape[-1]))
        return jax.random.uniform(k, shape, dtype, -limit, limit)

    e, f = spec.d_model, spec.d_ff
    keys = iter(jax.random.split(key, 2 + 6 * spec.num_layers))
    blocks = []
    for _ in range(spec.num_layers):
        blocks.append({
            "ln1_g": jnp.ones((e,), dtype), "ln1_b": jnp.zeros((e,), dtype),
            "wq": glorot(next(keys), (e, e)),
            "wk": glorot(next(keys), (e, e)),
            "wv": glorot(next(keys), (e, e)),
            "wo": glorot(next(keys), (e, e)),
            "ln2_g": jnp.ones((e,), dtype), "ln2_b": jnp.zeros((e,), dtype),
            "w1": glorot(next(keys), (e, f)), "b1": jnp.zeros((f,), dtype),
            "w2": glorot(next(keys), (f, e)), "b2": jnp.zeros((e,), dtype),
        })
    return {
        "embed": glorot(next(keys), (spec.vocab, e)),
        "blocks": blocks,
        "lnf_g": jnp.ones((e,), dtype), "lnf_b": jnp.zeros((e,), dtype),
        "head": glorot(next(keys), (e, spec.vocab)),
    }


def _layernorm(x: jax.Array, g: jax.Array, b: jax.Array) -> jax.Array:
    # fp32 statistics regardless of compute dtype (bf16 variance underflows).
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * g + b


def rope(x: jax.Array, positions: jax.Array, base: float) -> jax.Array:
    """Rotate dimension pairs of ``x [B, T, H, D]`` by angles
    ``positions[t] * base**(-2i/D)``. ``positions [T]`` are ABSOLUTE —
    a sequence shard passes ``offset + arange(T_local)`` and gets exactly
    the rotations its positions would receive in the full sequence.
    ``positions [B, T]`` rotates each batch element by its own positions
    — the decode path, where each serving slot sits at a different
    sequence length (ddl_tpu.serve). Positions need no upper bound: the
    rotation is stateless, so decode may run arbitrarily far past any
    training length (extrapolation pinned by tests/test_serve.py)."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"head_dim {d} must be even for RoPE")
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs  # [.., T, D/2]
    if angles.ndim == 2:  # shared positions: broadcast over batch
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)  # [B|1, T, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def apply_block(
    h: jax.Array,
    blk: Params,
    spec: LMSpec,
    *,
    attn_fn: AttnFn,
    positions: jax.Array,
    row_reduce=None,
    col_promote=None,
) -> jax.Array:
    """ONE pre-LN transformer block on residual stream ``h [B, T, E]`` —
    the layer unit both :func:`apply_lm` (whole stack, one device or
    sequence/tensor shards) and the pipeline stages (``ddl_tpu.pipeline``:
    a contiguous subset of layers per pp mesh position) apply, so a
    pipelined model can never drift from the oracle's per-layer math.
    The local head count is inferred from the (possibly tp-column-
    sharded) ``wq`` width; ``row_reduce``/``col_promote`` are Megatron's
    g/f hooks (see :func:`apply_lm`)."""
    b, t, _ = h.shape
    heads = lambda a: a.reshape(b, t, -1, spec.head_dim)
    reduce_ = row_reduce if row_reduce is not None else (lambda x: x)
    promote = col_promote if col_promote is not None else (lambda x: x)
    x = promote(_layernorm(h, blk["ln1_g"], blk["ln1_b"]))
    q = rope(heads(x @ blk["wq"]), positions, spec.rope_base)
    k = rope(heads(x @ blk["wk"]), positions, spec.rope_base)
    v = heads(x @ blk["wv"])
    a = attn_fn(q, k, v)
    h = h + reduce_(a.reshape(b, t, -1) @ blk["wo"])
    x = promote(_layernorm(h, blk["ln2_g"], blk["ln2_b"]))
    return h + reduce_(
        jax.nn.gelu(x @ blk["w1"] + blk["b1"]) @ blk["w2"]
    ) + blk["b2"]


def apply_lm(
    params: Params,
    tokens: jax.Array,
    spec: LMSpec = LMSpec(),
    *,
    attn_fn: AttnFn,
    pos_offset: int | jax.Array = 0,
    positions: jax.Array | None = None,
    compute_dtype=None,
    remat: bool = False,
    row_reduce=None,
    col_promote=None,
) -> jax.Array:
    """Forward pass: int tokens ``[B, T]`` -> fp32 logits ``[B, T, vocab]``.

    ``T`` may be the full sequence or a shard of it; ``pos_offset`` is the
    absolute position of element 0 (a traced ``lax.axis_index`` expression
    under ``shard_map``). A shard holding NON-contiguous positions (the
    ring's balanced zigzag layout, parallel/ring.zigzag_positions) passes
    the full per-token ``positions [T]`` instead, which overrides
    ``pos_offset`` — RoPE needs only absolute positions, never adjacency.
    ``attn_fn`` performs (possibly cross-shard) attention on post-RoPE
    ``[B, T, H, D]`` q/k/v and owns causal masking — the model applies no
    mask itself.

    ``row_reduce`` is the tensor-parallel hook (Megatron sharding,
    strategies/seq.py ``tensor_parallel``): when the caller hands this
    function COLUMN-sharded ``wq/wk/wv/w1`` (+ their biases) and
    ROW-sharded ``wo/w2`` slices, the attention output and MLP output
    are partial sums over the tp shards — ``row_reduce`` (Megatron's
    ``g``: ``collectives.tp_allreduce``, all-reduce forward / identity
    backward) completes them. ``col_promote`` is its CONJUGATE
    (Megatron's ``f``: ``collectives.tp_promote``, identity forward /
    all-reduce backward), applied where the tp-replicated residual
    stream enters the column-sharded matmuls — each tp member's branch
    produces only a PARTIAL input cotangent, and ``f`` completes the
    sum so LayerNorm params, earlier blocks and the embedding see full
    gradients even when the surrounding ``shard_map`` computes local
    (unreduced) grads. Everything else needs NO code change: the head
    count is inferred from the local ``wq`` width, so each shard
    attends its own head subset, and the residual stream stays
    full-width (tp-invariant) on every device. ``None`` (default) =
    no tensor parallelism.

    ``remat=True`` wraps each block in ``jax.checkpoint``: the backward
    pass recomputes the block — INCLUDING the cross-shard attention's
    collective sweep (the ring's ppermute chain replays) — instead of
    saving its residuals. This is the long-context memory lever: the
    saved state per block drops from the attention residuals (the ring's
    O((T/P)^2)-per-step tiles, O(T^2/P) per device across the sweep) to
    the block INPUT (O(T/P · d_model)), at ~1/3 extra FLOPs (one extra
    forward per block) — the standard remat trade
    (jax-ml.github.io/scaling-book; measured by
    tests/test_lm.py::test_seq_trainer_remat_*).
    """
    if compute_dtype is not None:
        params = jax.tree.map(lambda p: p.astype(compute_dtype), dict(params))
    h = params["embed"][tokens]  # [B, T, E]
    _, t, _ = h.shape
    if positions is None:
        positions = pos_offset + jnp.arange(t)

    def block(h, blk):
        return apply_block(
            h, blk, spec, attn_fn=attn_fn, positions=positions,
            row_reduce=row_reduce, col_promote=col_promote,
        )

    if remat:
        block = jax.checkpoint(block)
    for blk in params["blocks"]:
        h = block(h, blk)
    h = _layernorm(h, params["lnf_g"], params["lnf_b"])
    return (h @ params["head"]).astype(jnp.float32)


def apply_lm_cached(
    params: Params,
    tokens: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_pos: jax.Array,
    spec: LMSpec = LMSpec(),
    *,
    start: jax.Array,
    positions: jax.Array | None = None,
    rows: jax.Array | None = None,
    compute_dtype=None,
    row_reduce=None,
    last_row: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Incremental (KV-cached) forward — the serving twin of
    :func:`apply_lm`: int tokens ``[B, T]`` -> fp32 logits
    ``[B, T, vocab]`` plus the updated cache. ``T`` is the number of NEW
    sequence elements per slot (a whole prompt at prefill, one token per
    decode step); everything already processed lives in the cache.
    With ``last_row`` (a traced scalar) the head is applied to that one
    of the ``T`` rows alone and the logits are ``[B, 1, vocab]``: a
    prefill samples from its last real row, so the ``[T, vocab]``
    product is never formed.

    ``compute_dtype`` casts every weight inside the program; a caller may
    hand over weights already in that dtype (the serve engine places
    them so), and the cast is then nothing.

    ``cache_k``/``cache_v [num_layers, B, C, H, D]`` are the per-layer
    ring buffers and ``cache_pos [B, C]`` the absolute position each row
    holds (``ops.kv_cache.PAD_POS`` = unwritten/stale; the attend masks
    on positions, so stale rows are invisible). ``start [B]`` is each
    slot's write cursor: token t lands in row ``(start + t) % C`` at
    absolute position ``start + t``. ``positions [B, T]`` overrides the
    per-token absolute positions (RoPE + the stored mask positions)
    without moving the write rows — pass ``PAD_POS`` at padded prompt
    tails so they are never attended, or far-past-training values to
    probe RoPE extrapolation. ``rows [B, T]`` overrides the write rows
    themselves (decoupling both from ``start``) — the offset-prefill
    path (``serve.engine``: prefill resuming at a nonzero position base
    after a prefix-cache copy or an earlier chunk) uses it to redirect
    PADDED bucket tails to row ``C`` (out of bounds — the scatter DROPS
    them), so a power-of-two bucket overhanging the capacity can never
    wrap onto live prefix rows.

    Parity contract: one prefill of ``tokens[:, :n]`` followed by
    one-token decode steps reproduces full-forward :func:`apply_lm`
    logits at every position to tight tolerance — the same LN/RoPE/
    einsum/mask numerics, just read from the cache
    (tests/test_serve.py pins it for tp=1 and tp=2).

    ``row_reduce`` is the same Megatron ``g`` hook as :func:`apply_lm`
    (all-reduce over tp of the row-sharded attention/MLP outputs); its
    conjugate ``f`` is identity in the forward, and this path is never
    differentiated, so there is no ``col_promote`` here. Under tensor
    parallelism the caches hold each device's LOCAL head subset — the
    cache pytree is tp-sharded exactly like ``wq`` (ddl_tpu.serve.cache).
    """
    from ..ops import kv_cache

    if compute_dtype is not None:
        params = jax.tree.map(lambda p: p.astype(compute_dtype), dict(params))
    h = params["embed"][tokens]  # [B, T, E]
    b, t, e = h.shape
    capacity = cache_k.shape[2]
    if rows is None:
        rows = (start[:, None] + jnp.arange(t, dtype=start.dtype)) % capacity
    if positions is None:
        positions = start[:, None] + jnp.arange(t, dtype=start.dtype)
    cache_pos = jax.vmap(lambda p, r, v: p.at[r].set(v))(
        cache_pos, rows, positions.astype(cache_pos.dtype)
    )
    heads = lambda a: a.reshape(b, t, -1, spec.head_dim)
    reduce_ = row_reduce if row_reduce is not None else (lambda x: x)

    for i, blk in enumerate(params["blocks"]):
        x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
        q = rope(heads(x @ blk["wq"]), positions, spec.rope_base)
        k = rope(heads(x @ blk["wk"]), positions, spec.rope_base)
        v = heads(x @ blk["wv"])
        ck = kv_cache.append_rows(cache_k[i], k.astype(cache_k.dtype), rows)
        cv = kv_cache.append_rows(cache_v[i], v.astype(cache_v.dtype), rows)
        cache_k = cache_k.at[i].set(ck)
        cache_v = cache_v.at[i].set(cv)
        a = kv_cache.attend(q, ck.astype(q.dtype), cv.astype(q.dtype),
                            positions, cache_pos)
        h = h + reduce_(a.reshape(b, t, -1) @ blk["wo"])
        x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        h = h + reduce_(
            jax.nn.gelu(x @ blk["w1"] + blk["b1"]) @ blk["w2"]
        ) + blk["b2"]

    return _head_logits(params, h, last_row), cache_k, cache_v, cache_pos


def _head_logits(params, h, last_row=None):
    """The final norm and the head of the cached forwards: fp32 logits
    of every row of ``h [B, T, E]``, or of row ``last_row`` alone."""
    if last_row is not None:
        h = jax.lax.dynamic_slice_in_dim(h, last_row, 1, axis=1)
    h = _layernorm(h, params["lnf_g"], params["lnf_b"])
    return (h @ params["head"]).astype(jnp.float32)


def apply_lm_paged(
    params: Params,
    tokens: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    pool_pos: jax.Array,
    table: jax.Array,
    spec: LMSpec = LMSpec(),
    *,
    positions: jax.Array,
    flat_rows: jax.Array,
    compute_dtype=None,
    row_reduce=None,
    pool_k_scale: jax.Array | None = None,
    pool_v_scale: jax.Array | None = None,
    platform: str | None = None,
    last_row: jax.Array | None = None,
) -> tuple[jax.Array, ...]:
    """Incremental forward against the PAGED (block-table) KV pool — the
    same layer math as :func:`apply_lm_cached` (``last_row`` likewise:
    logits ``[B, 1, vocab]`` of that row alone; ``compute_dtype``
    likewise: weights already in it, as the serve engine places them,
    cast to nothing), with the per-slot ring replaced by one shared pool
    read/written through a block table:

    ``pool_k``/``pool_v [num_layers, pages, page_size, H, D]`` and
    ``pool_pos [pages, page_size]`` are the shared pool
    (``ddl_tpu.serve.cache.PagedKVCache``); ``table [B, TP]`` holds each
    slot's page ids in logical order (``-1`` = unmapped — ``TP`` is the
    PAGE-COUNT bucket, the compiled program's static key). New tokens
    write at ``flat_rows [B, T]`` (``ops.kv_cache.table_rows`` of the
    logical rows — out-of-bounds rows drop, which is how padded bucket
    tails and inactive decode slots vanish), and attention gathers each
    slot's pages back into a ``[B, TP * page_size, ...]`` view whose
    positions travel with the rows (``table_positions``) — so
    ``ops.kv_cache.attend`` runs UNCHANGED and the masking/eviction
    semantics are exactly the contiguous cache's.

    **When no view is gathered** (ISSUE 31). It is one algorithm, causal
    attention over a block table, whose best form depends on what the
    shapes show, decided here at trace time: with ONE query a slot (``T
    == 1``: a decode program), a pool that is not int8 (no scale
    planes), widths ``ops.paged_attention.kernel_accepts`` (``head_dim``
    whole 128-lane tiles, the local heads whole 8-row tiles) and
    ``platform == "tpu"``, each layer's attention is
    ``ops.paged_attention.paged_decode_attention``: a Pallas kernel that
    reads each slot's mapped pages where they lie in the stack, through
    the table, under ``attend``'s own rule (``k_pos <= q_pos`` from the
    page's positions), and writes no view. Everything else — whole-
    prompt and chunked prefill, speculation's verifier (``T > 1``), the
    int8 pool, narrow heads, every CPU run — gathers the views and
    calls ``attend`` as before, its program byte-identical to what it
    was. ``platform`` is the platform of the devices the program will
    run on (``mesh.devices.flat[0].platform``: what the engine passes),
    ``None`` falling back to ``jax.default_backend()``, as
    ``ops.attention.flash_attention_bthd`` resolves it.

    **The stacked pool is updated in place** (ISSUE 29): layer ``i``
    never leaves the ``[L, pages, ...]`` arrays. Its fresh rows scatter
    straight into the stack at flat row ``i * pages * page_size +
    flat_rows`` and its pages gather straight out of it through ``i *
    pages + table`` (``write_rows_flat`` / ``gather_pages`` with
    ``layer=i``), so with the pools donated a program moves the rows it
    writes and the pages it reads, not a layer's whole pool out and
    back. ``flat_rows`` and ``table`` stay PER-LAYER indices, the same
    for every layer; a dropped row (``>= pages * page_size``) goes out
    of bounds of the whole stack (``L * pages * page_size``), never
    into layer ``i + 1``'s row 0. Values stored, view gathered and
    every dtype are what ``pool[i]`` / ``.at[i].set`` gave: logits and
    pools are bitwise the same (pinned in tests/test_serve_paged.py).

    Parity contract: bitwise-identical logits to :func:`apply_lm_cached`
    over the same resident history, at ANY page-count bucket — masked
    padding contributes exactly 0 (verified on this backend; pinned
    paged ≡ contiguous through the whole serving stack in
    tests/test_serve_paged.py). Never differentiated; ``row_reduce`` is
    the same Megatron ``g`` hook as :func:`apply_lm_cached`.

    **Int8 pool** (ISSUE 19, ``ServeConfig.kv_dtype``): passing the
    per-head fp32 scale planes ``pool_k_scale``/``pool_v_scale [L, P,
    page, H]`` switches the storage path — fresh rows quantize on write
    (``ops.kv_cache.quantize_rows``: per-head absmax, int8 payload +
    fp32 scale), the gathered attend view dequantizes back to the
    compute dtype, and the return grows to ``(logits, pool_k, pool_v,
    pool_pos, pool_k_scale, pool_v_scale)``. The branch is STATIC
    (scales are a trace-time ``None`` check), so the fp32/bf16 program
    is byte-identical with the feature off. Quantization error enters
    ONLY through the attend's K/V operands — masking, positions and
    the layer math are untouched, and a row read back dequantizes to
    the same values on every reader (sharing/hand-off stay bit-exact
    because the bytes themselves travel)."""
    from ..ops import kv_cache, paged_attention

    if (pool_k_scale is None) != (pool_v_scale is None):
        raise ValueError("pass both pool_k_scale and pool_v_scale or neither")
    quantized = pool_k_scale is not None
    if compute_dtype is not None:
        params = jax.tree.map(lambda p: p.astype(compute_dtype), dict(params))
    h = params["embed"][tokens]  # [B, T, E]
    b, t, _ = h.shape
    if platform is None:
        platform = jax.default_backend()
    in_place = (
        t == 1 and not quantized and platform == "tpu"
        and paged_attention.kernel_accepts(
            pool_k.shape[3], spec.head_dim, pool_k.shape[2]))
    pool_pos = kv_cache.write_rows_flat(
        pool_pos, positions.astype(pool_pos.dtype), flat_rows
    )
    if not in_place:
        k_pos = kv_cache.table_positions(pool_pos, table)  # [B, TP * page]
    heads = lambda a: a.reshape(b, t, -1, spec.head_dim)
    reduce_ = row_reduce if row_reduce is not None else (lambda x: x)

    for i, blk in enumerate(params["blocks"]):
        x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
        q = rope(heads(x @ blk["wq"]), positions, spec.rope_base)
        k = rope(heads(x @ blk["wk"]), positions, spec.rope_base)
        v = heads(x @ blk["wv"])
        if quantized:
            kq, ks = kv_cache.quantize_rows(k)
            vq, vs = kv_cache.quantize_rows(v)
            pool_k = kv_cache.write_rows_flat(
                pool_k, kq, flat_rows, layer=i)
            pool_v = kv_cache.write_rows_flat(
                pool_v, vq, flat_rows, layer=i)
            pool_k_scale = kv_cache.write_rows_flat(
                pool_k_scale, ks, flat_rows, layer=i)
            pool_v_scale = kv_cache.write_rows_flat(
                pool_v_scale, vs, flat_rows, layer=i)
            k_view = kv_cache.dequantize_rows(
                kv_cache.gather_pages(pool_k, table, layer=i),
                kv_cache.gather_pages(pool_k_scale, table, layer=i), q.dtype,
            )
            v_view = kv_cache.dequantize_rows(
                kv_cache.gather_pages(pool_v, table, layer=i),
                kv_cache.gather_pages(pool_v_scale, table, layer=i), q.dtype,
            )
        else:
            pool_k = kv_cache.write_rows_flat(
                pool_k, k.astype(pool_k.dtype), flat_rows, layer=i)
            pool_v = kv_cache.write_rows_flat(
                pool_v, v.astype(pool_v.dtype), flat_rows, layer=i)
            if not in_place:
                k_view = kv_cache.gather_pages(
                    pool_k, table, layer=i).astype(q.dtype)
                v_view = kv_cache.gather_pages(
                    pool_v, table, layer=i).astype(q.dtype)
        if in_place:
            a = paged_attention.paged_decode_attention(
                q[:, 0], pool_k, pool_v, pool_pos, table, positions[:, 0],
                i)[:, None]
        else:
            a = kv_cache.attend(q, k_view, v_view, positions, k_pos)
        h = h + reduce_(a.reshape(b, t, -1) @ blk["wo"])
        x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        h = h + reduce_(
            jax.nn.gelu(x @ blk["w1"] + blk["b1"]) @ blk["w2"]
        ) + blk["b2"]

    logits = _head_logits(params, h, last_row)
    if quantized:
        return (logits, pool_k, pool_v, pool_pos,
                pool_k_scale, pool_v_scale)
    return logits, pool_k, pool_v, pool_pos


def ce_sums(
    logits: jax.Array, targets: jax.Array, weights: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Weighted cross-entropy of fp32 ``logits [B, T, V]`` against
    ``targets [B, T]`` as ``(sum_ce, sum_weights)`` — the accumulator
    form behind :func:`lm_loss_sums`, exposed so the pipeline's last
    stage (which holds logits but not the whole model) scores with
    EXACTLY the oracle's loss math."""
    logprobs = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
    w = weights.astype(jnp.float32)
    return jnp.sum(ce * w), jnp.sum(w)


def lm_loss_sums(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    weights: jax.Array,
    spec: LMSpec = LMSpec(),
    *,
    attn_fn: AttnFn,
    pos_offset: int | jax.Array = 0,
    positions: jax.Array | None = None,
    compute_dtype=None,
    remat: bool = False,
    row_reduce=None,
    col_promote=None,
) -> tuple[jax.Array, jax.Array]:
    """Weighted next-token cross-entropy as ``(sum_ce, sum_weights)`` —
    the accumulator form, so the caller owns normalization: a single
    device divides directly; a sequence shard ``psum``s both over the
    mesh axis first (mean of per-shard means would be wrong whenever the
    loss mask is unevenly distributed across shards, as it is for the
    copy task where only second-half positions are scored)."""
    logits = apply_lm(
        params, tokens, spec, attn_fn=attn_fn, pos_offset=pos_offset,
        positions=positions, compute_dtype=compute_dtype, remat=remat,
        row_reduce=row_reduce, col_promote=col_promote,
    )
    return ce_sums(logits, targets, weights)


def lm_correct_sums(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    weights: jax.Array,
    spec: LMSpec = LMSpec(),
    *,
    attn_fn: AttnFn,
    pos_offset: int | jax.Array = 0,
    positions: jax.Array | None = None,
    compute_dtype=None,
    remat: bool = False,
    row_reduce=None,
    col_promote=None,
) -> tuple[jax.Array, jax.Array]:
    """Weighted top-1 next-token hits as ``(sum_correct, sum_weights)``
    (accumulator form, same contract as :func:`lm_loss_sums` — and the
    analogue of ``cnn.correct_count``). ``remat`` is accepted for
    signature symmetry with :func:`lm_loss_sums` (the trainer builds
    both through one helper); it changes nothing in this never-
    differentiated eval path."""
    logits = apply_lm(
        params, tokens, spec, attn_fn=attn_fn, pos_offset=pos_offset,
        positions=positions, compute_dtype=compute_dtype, remat=remat,
        row_reduce=row_reduce, col_promote=col_promote,
    )
    hits = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    w = weights.astype(jnp.float32)
    return jnp.sum(hits * w), jnp.sum(w)
