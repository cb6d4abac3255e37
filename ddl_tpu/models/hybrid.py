"""The second decoder family: window and global attention layers mixed by
a per-layer pattern, routed experts, written once over a cache view.

Where ``models.transformer`` writes its dense block three times (full,
contiguous cache, paged cache), this family has ONE block function,
:func:`apply_block`, over a (mixer kind, FFN kind) pair a layer. The
block projects, rotates and normalises; what it attends over is the
caller's: ``mix(layer, q, k, v) -> a`` is the cache view. The uncached
forward (:func:`apply_hybrid`, the tests' oracle) passes plain attention
over the call's own rows; the serving engine passes
:class:`PagedMixer`, which writes the rows into two kinds of page pool
and attends what they hold.

The block (every symbol a field of :class:`HybridSpec`): ``h = x +
Attn(RMS(x))``, ``y = h + FFN(RMS(h))``, no bias anywhere, a final RMS
and an untied head with fp32 logits.

- **Attention.** ``num_heads`` query heads of ``head_dim``; K heads of
  ``head_dim`` and V heads of ``v_head_dim``, ``kv_heads_global`` or
  ``kv_heads_window`` of them by the layer's kind; V is scaled by
  ``value_scale``; rotary on the first ``rotary_dim`` dimensions of each
  Q and K head (pairs ``(i, i + rotary_dim / 2)``) with a base per kind.
  A global layer attends every earlier position, a window layer the
  last ``window`` (its own included) and adds a learned sink logit a
  head to the softmax's denominator.
- **FFN.** Dense gated SiLU of width ``d_ff``, or ``num_experts`` routed
  experts of width ``expert_ff``, ``experts_per_token`` a token
  (``ops.moe``). The layer holds the experts ``experts_held[0] ..
  experts_held[1] - 1`` and computes their part alone.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import kv_cache, moe

GLOBAL, WINDOW = 0, 1   # layer_kinds, as the published pattern counts them
DENSE, MOE = 0, 1       # ffn_kinds


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """Shapes and the per-layer pattern. The defaults are the tests'
    toy; :data:`NAMED_SPECS` has the published widths."""

    vocab: int = 64
    d_model: int = 32
    num_heads: int = 4
    head_dim: int = 12
    v_head_dim: int = 8
    kv_heads_global: int = 1
    kv_heads_window: int = 2
    window: int = 8
    rope_base_global: float = 5_000_000.0
    rope_base_window: float = 10_000.0
    rotary_dim: int = 4
    value_scale: float = 0.707
    d_ff: int = 64
    expert_ff: int = 16
    num_experts: int = 16
    experts_per_token: int = 4
    experts_held: tuple[int, int] = (0, 16)   # first, one past the last
    layer_kinds: tuple[int, ...] = (GLOBAL, WINDOW, WINDOW, GLOBAL)
    ffn_kinds: tuple[int, ...] = (DENSE, MOE, MOE, MOE)
    norm_eps: float = 1e-5

    def __post_init__(self):
        if len(self.layer_kinds) != len(self.ffn_kinds):
            raise ValueError("layer_kinds and ffn_kinds differ in length")
        first, end = self.experts_held
        if not 0 <= first < end <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"[0, {self.num_experts}]")
        for name in ("kv_heads_global", "kv_heads_window"):
            if self.num_heads % getattr(self, name):
                raise ValueError(f"num_heads ({self.num_heads}) must be a "
                                 f"multiple of {name}")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary_dim ({self.rotary_dim}) must be even "
                             f"and at most head_dim ({self.head_dim})")
        w = self.window
        if w < 8 or w & (w - 1):
            raise ValueError(f"window ({w}) must be a power of two >= 8: "
                             "a window layer's prefill works on blocks of "
                             "one window")

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def kv_heads(self, layer: int) -> int:
        return (self.kv_heads_window if self.layer_kinds[layer] == WINDOW
                else self.kv_heads_global)

    def layers_of(self, kind: int) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    def block_shapes(self, layer: int) -> dict[str, tuple[int, ...]]:
        """Every leaf of one layer's weights, by name."""
        e, hq, hkv = self.d_model, self.num_heads, self.kv_heads(layer)
        out = {"ln1": (e,), "ln2": (e,),
               "wq": (e, hq * self.head_dim), "wk": (e, hkv * self.head_dim),
               "wv": (e, hkv * self.v_head_dim),
               "wo": (hq * self.v_head_dim, e)}
        if self.layer_kinds[layer] == WINDOW:
            out["sink"] = (hq,)
        if self.ffn_kinds[layer] == DENSE:
            out.update(wg=(e, self.d_ff), wu=(e, self.d_ff),
                       wd=(self.d_ff, e))
        else:
            f, h = self.expert_ff, self.held
            out.update(wr=(e, self.num_experts), rc=(self.num_experts,),
                       eg=(h, e, f), eu=(h, e, f), ed=(h, f, e))
        return out

    @property
    def num_params(self) -> int:
        blocks = sum(math.prod(s) for i in range(self.num_layers)
                     for s in self.block_shapes(i).values())
        return 2 * self.vocab * self.d_model + self.d_model + blocks


# The published widths, cut to what one chip of a stated deployment
# holds (perf/configs/ has the deployment and every assumption).
NAMED_SPECS = {
    "mimo-v2-flash-ep16": HybridSpec(
        vocab=19072, d_model=4096, num_heads=64, head_dim=192,
        v_head_dim=128, kv_heads_global=4, kv_heads_window=8, window=128,
        rope_base_global=5_000_000.0, rope_base_window=10_000.0,
        rotary_dim=64, value_scale=0.707, d_ff=16384, expert_ff=2048,
        num_experts=256, experts_per_token=8, experts_held=(0, 16),
        layer_kinds=(GLOBAL, WINDOW, WINDOW, WINDOW, WINDOW, WINDOW, GLOBAL),
        ffn_kinds=(DENSE, MOE, MOE, MOE, MOE, MOE, MOE)),
}


def init_hybrid_params(key: jax.Array, spec: HybridSpec) -> dict:
    """Random fp32 weights: Glorot matrices, unit gains, small non-zero
    sinks and router corrections (zeros would leave both inert), and an
    embedding of unit variance: a smaller one leaves the residual stream
    to the context's mean, every token then chooses the same few experts,
    and most of them are never chosen."""
    def leaf(k, name, shape):
        if name in ("ln1", "ln2"):
            return jnp.ones(shape, jnp.float32)
        if name == "embed":
            return jax.random.normal(k, shape, jnp.float32)
        if name in ("sink", "rc"):
            return 0.02 * jax.random.normal(k, shape, jnp.float32)
        limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(k, shape, jnp.float32, -limit, limit)

    keys = jax.random.split(key, spec.num_layers + 2)
    blocks = []
    for i in range(spec.num_layers):
        shapes = spec.block_shapes(i)
        ks = jax.random.split(keys[i], len(shapes))
        blocks.append({n: leaf(k, n, s)
                       for k, (n, s) in zip(ks, shapes.items())})
    e, v = spec.d_model, spec.vocab
    return {"embed": leaf(keys[-2], "embed", (v, e)), "blocks": blocks,
            "lnf": jnp.ones((e,), jnp.float32),
            "head": leaf(keys[-1], "head", (e, v))}


def rms_norm(x, g, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def partial_rope(x, positions, base: float, rotary_dim: int):
    """Rotate the first ``rotary_dim`` dimensions of each head of ``x [B,
    T, H, D]`` by ``positions [B, T]``, pairing ``(i, i + rotary_dim /
    2)``; the rest pass through."""
    half = rotary_dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rotary_dim]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rotary_dim:]], -1)


def apply_block(h, blk, spec: HybridSpec, layer: int, positions, real, mix):
    """One layer on ``h [B, T, E]`` at ``positions [B, T]`` (``real [B,
    T]``: not padding). ``mix(layer, q, k, v)`` is the cache view: it
    keeps ``k``/``v`` where it keeps them and returns the attention
    output ``[B, T, Hq, Dv]``. Returns ``(h, counts)``, ``counts`` the
    routed FFN's ``(assigned, touched)`` or ``None``."""
    b, t, _ = h.shape
    window = spec.layer_kinds[layer] == WINDOW
    base = spec.rope_base_window if window else spec.rope_base_global
    heads = lambda a, d: a.reshape(b, t, -1, d)
    x = rms_norm(h, blk["ln1"], spec.norm_eps)
    q = partial_rope(heads(x @ blk["wq"], spec.head_dim), positions, base,
                     spec.rotary_dim)
    k = partial_rope(heads(x @ blk["wk"], spec.head_dim), positions, base,
                     spec.rotary_dim)
    v = heads(x @ blk["wv"], spec.v_head_dim) * jnp.asarray(
        spec.value_scale, x.dtype)
    a = mix(layer, q, k, v)
    h = h + a.reshape(b, t, -1) @ blk["wo"]
    x = rms_norm(h, blk["ln2"], spec.norm_eps)
    if spec.ffn_kinds[layer] == DENSE:
        up = jax.nn.silu(x @ blk["wg"]) * (x @ blk["wu"])
        return h + up @ blk["wd"], None
    flat = x.reshape(b * t, -1)
    experts, weights = moe.route(flat, blk["wr"], blk["rc"],
                                 spec.experts_per_token)
    out, counts = moe.routed_ffn(
        flat, blk["eg"], blk["eu"], blk["ed"], experts, weights,
        real.reshape(-1), first=spec.experts_held[0],
        tile=moe.tile_rows(b * t, spec.experts_per_token, spec.num_experts))
    return h + out.reshape(h.shape).astype(h.dtype), counts


def apply_layers(params, tokens, spec: HybridSpec, positions, real, mix,
                 compute_dtype=None):
    """Embedding, every block, the final norm: ``(h [B, T, E], counts
    int32 [2])``, ``counts`` summed over the routed layers."""
    h = params["embed"][tokens]
    if compute_dtype is not None:
        h = h.astype(compute_dtype)
    counts = jnp.zeros(2, jnp.int32)
    for i, blk in enumerate(params["blocks"]):
        h, c = apply_block(h, blk, spec, i, positions, real, mix)
        if c is not None:
            counts = counts + c
    return rms_norm(h, params["lnf"], spec.norm_eps), counts


def head_logits(params, h):
    return (h @ params["head"]).astype(jnp.float32)


def apply_hybrid(params, tokens, spec: HybridSpec, compute_dtype=None):
    """The uncached forward: ``tokens [B, T]`` -> ``(logits fp32 [B, T,
    V], counts)``. Attention over the call's own rows, nothing kept."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def mix(layer, q, k, v):
        window = spec.layer_kinds[layer] == WINDOW
        return kv_cache.attend_grouped(
            q, k, v, positions, positions,
            window=spec.window if window else None,
            sink=params["blocks"][layer].get("sink"))

    h, counts = apply_layers(params, tokens, spec, positions,
                             jnp.ones((b, t), bool), mix, compute_dtype)
    return head_logits(params, h), counts


# -- the paged cache view -----------------------------------------------------
#
# Two page groups side by side. The GLOBAL group keeps every row: a slot's
# block table maps logical page j to a pool page, as the dense family's
# does, and a row's position is its logical row. The WINDOW group keeps
# the last `window` rows: a slot's table is a ring of R columns, logical
# page j lives in column j % R, and the host frees a column's page once
# the page lies wholly behind the window. No position travels with a row:
# a column's logical page follows from the last position written, and
# whatever else a pool page still holds computes to a position behind the
# window or ahead of the query, which the mask drops.

GLOBAL_QUERY_BLOCK = 256


class PagedMixer:
    """The cache view of one prefill or decode call. ``pools`` maps a
    layer to its ``(k, v)`` pool; after the layers have run it holds the
    updated pools."""

    def __init__(self, spec: HybridSpec, params, pools: dict, *, page_size,
                 g_table, w_table, positions, real, last, base=None):
        self.spec, self.params, self.pools = spec, params, dict(pools)
        self.ps = page_size
        self.g_table, self.w_table = g_table, w_table
        self.positions = positions          # [B, T]; -1 where padding
        self.base = base                    # prefill: the block's first
        cols = jnp.arange(g_table.shape[1] * page_size, dtype=jnp.int32)
        self.g_pos = jnp.where(cols[None, :] <= last[:, None], cols[None, :],
                               -1)
        self.g_rows = kv_cache.table_rows(
            g_table, jnp.where(real, positions, -1), page_size,
            self._pages(GLOBAL))
        keep = real & (positions > last[:, None] - spec.window)
        self.w_rows = kv_cache.ring_rows(
            w_table, jnp.where(keep, positions, -1), page_size,
            self._pages(WINDOW))
        self.w_pos = kv_cache.ring_positions(last, w_table.shape[1],
                                             page_size)

    def _pages(self, kind: int) -> int:
        """Pages of one group's pools (0 where the pattern has no such
        layer: its rows then never resolve to a write)."""
        layers = self.spec.layers_of(kind)
        return self.pools[layers[0]][0].shape[0] if layers else 0

    def _write(self, layer, k, v, rows):
        """A pool row is all of a token's heads side by side (a whole
        number of 128-lane tiles at the published widths, so the scatter
        works on the pool as it lies in memory)."""
        pk, pv = self.pools[layer]
        flat = lambda a, pool: a.reshape(*a.shape[:2], -1).astype(pool.dtype)
        pk = kv_cache.write_rows_flat(pk, flat(k, pk), rows)
        pv = kv_cache.write_rows_flat(pv, flat(v, pv), rows)
        self.pools[layer] = (pk, pv)
        return pk, pv

    def _view(self, pool, table, like):
        """A slot's rows through its table, heads apart: ``[B, C, Hkv,
        D]`` in ``like``'s dtype and head width."""
        g = kv_cache.gather_pages(pool, table)
        return g.reshape(*g.shape[:2], -1, like.shape[-1]).astype(like.dtype)

    def __call__(self, layer, q, k, v):
        if self.spec.layer_kinds[layer] == GLOBAL:
            return self._global(layer, q, k, v)
        if self.base is None:
            return self._window_decode(layer, q, k, v)
        return self._window_prefill(layer, q, k, v)

    def _global(self, layer, q, k, v):
        """Write, then attend the slot's whole table; a prefill's
        queries go in blocks so no ``[H, T, T]`` array exists."""
        pk, pv = self._write(layer, k, v, self.g_rows)
        kv = self._view(pk, self.g_table, k)
        vv = self._view(pv, self.g_table, v)
        b, t = self.positions.shape
        blk = min(t, GLOBAL_QUERY_BLOCK)
        if b != 1 or t == blk:
            return kv_cache.attend_grouped(q, kv, vv, self.positions,
                                           self.g_pos)
        qb = q.reshape(t // blk, 1, blk, *q.shape[2:])
        pb = self.positions.reshape(t // blk, 1, blk)
        out = lax.map(lambda a: kv_cache.attend_grouped(
            a[0], kv, vv, a[1], self.g_pos), (qb, pb))
        return out.reshape(1, t, *out.shape[3:])

    def _window_decode(self, layer, q, k, v):
        pk, pv = self._write(layer, k, v, self.w_rows)
        return kv_cache.attend_grouped(
            q, self._view(pk, self.w_table, k), self._view(pv, self.w_table, v),
            self.positions, self.w_pos, window=self.spec.window,
            sink=self.params["blocks"][layer]["sink"])

    def _window_prefill(self, layer, q, k, v):
        """One slot's block of ``T`` rows on the band: a query block of
        one window against its own rows and the window before them, the
        first block's from the ring as it stood before this call. Only
        the last ``window`` rows are written."""
        w, ps = self.spec.window, self.ps
        pk, pv = self.pools[layer]
        t = q.shape[1]
        old = self.base - w + jnp.arange(w, dtype=jnp.int32)  # positions
        at = jnp.maximum(old, 0)
        idx = (at // ps) % self.w_table.shape[1] * ps + at % ps
        ek = jnp.concatenate([self._view(pk, self.w_table, k)[0, idx], k[0]])
        ev = jnp.concatenate([self._view(pv, self.w_table, v)[0, idx], v[0]])
        epos = jnp.concatenate([jnp.where(old >= 0, old, -1),
                                self.positions[0]])
        self._write(layer, k, v, self.w_rows)
        sink = self.params["blocks"][layer]["sink"]
        if t <= w:
            return kv_cache.attend_grouped(
                q, ek[None], ev[None], self.positions, epos[None],
                window=w, sink=sink)
        nb = t // w

        def band(a):
            a = a.reshape(nb + 1, w, *a.shape[1:])
            return jnp.concatenate([a[:-1], a[1:]], axis=1)    # [nb, 2W, ..]

        out = kv_cache.attend_grouped(
            q.reshape(nb, w, *q.shape[2:]), band(ek), band(ev),
            self.positions.reshape(nb, w), band(epos), window=w, sink=sink)
        return out.reshape(1, t, *out.shape[2:])


def apply_hybrid_paged(params, pools: dict, tokens, spec: HybridSpec, *,
                       page_size: int, g_table, w_table, positions, real,
                       last, base=None, compute_dtype=None):
    """The serving forward of one call through :class:`PagedMixer`:
    ``tokens [B, T]`` at ``positions [B, T]`` -> ``(h [B, T, E], pools,
    counts)``. ``base`` (a traced scalar) marks a prefill of one slot
    from that position; ``None`` a decode of one row a slot. ``last [B]``
    is the last position this call writes (behind 0: nothing)."""
    mix = PagedMixer(spec, params, pools, page_size=page_size,
                     g_table=g_table, w_table=w_table, positions=positions,
                     real=real, last=last, base=base)
    h, counts = apply_layers(params, tokens, spec,
                             jnp.maximum(positions, 0), real, mix,
                             compute_dtype)
    return h, mix.pools, counts
