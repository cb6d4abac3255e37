"""Routed experts for one member of an expert-parallel group.

The layer is told which experts it holds (a contiguous range), routes
every token over ALL of them, and computes the part of the result that
its own experts give; what the absent experts would add is left out and
nothing stands in for them or for their exchange.

- :func:`route`: fp32 sigmoid scores, the top ``k`` of ``score +
  correction`` (the correction selects, it never weighs), weights the
  selected scores normalised to sum to one, times the model's scaling
  factor where it has one.
- :func:`routed_ffn`: the assignments to held experts, sorted by expert,
  each expert's run padded to whole row tiles, and one gated-SiLU product
  chain a tile against that tile's expert. The loop runs over the tiles
  that exist (a dynamic count), so a decode tick touches only the experts
  its tokens chose and a prefill pays for its own assignments, not for a
  worst case; no assignment is dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route(x, wr, correction, top_k: int, scale: float | None = None):
    """``x [T, E]``, ``wr [E, N]``, ``correction [N]`` -> ``(experts
    int32 [T, k], weights fp32 [T, k])``. All in fp32 at the highest
    matmul precision: a score that rounding moves swaps an expert. With
    ``scale`` the weights are ``scale x score / (sum of the chosen
    scores + 1e-20)``, as the models that publish a scaling factor
    write them."""
    sc = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), wr.astype(jnp.float32),
                                precision=lax.Precision.HIGHEST))
    _, experts = lax.top_k(sc + correction.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sc, experts, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    if scale is None:
        return experts.astype(jnp.int32), w / total
    return experts.astype(jnp.int32), scale * w / (total + 1e-20)


def tile_rows(tokens: int, top_k: int, num_experts: int) -> int:
    """Rows a tile: the power of two at or above an expert's expected
    share of the call's assignments, between 8 and 256."""
    want, tile = max(1, tokens * top_k // num_experts), 8
    while tile < min(want, 256):
        tile *= 2
    return tile


def routed_ffn(x, wg, wu, wd, experts, weights, real, *, first: int,
               tile: int):
    """The held experts' part of the layer's output, fp32 ``[T, E]``,
    and ``(assigned, touched)``: the assignments computed and the held
    experts with at least one.

    ``x [T, E]``; ``wg``/``wu [H, E, F]``, ``wd [H, F, E]`` for the ``H``
    experts ``first .. first + H - 1``; ``experts``/``weights [T, k]``
    from :func:`route`; ``real [T]`` masks rows that are padding."""
    t, k = experts.shape
    held_n = wg.shape[0]
    local = experts - first
    held = (local >= 0) & (local < held_n) & real[:, None]
    group = jnp.where(held, local, held_n).reshape(-1)        # [T * k]
    order = jnp.argsort(group, stable=True).astype(jnp.int32)  # held first
    counts = jnp.zeros(held_n + 1, jnp.int32).at[group].add(1)[:held_n]
    starts = jnp.cumsum(counts) - counts          # of each run, in `order`
    tiles = -(-counts // tile)
    tile_ends = jnp.cumsum(tiles)
    w_flat = weights.reshape(-1)
    lane = jnp.arange(tile, dtype=jnp.int32)

    def one(i, out):
        e = jnp.searchsorted(tile_ends, i, side="right").astype(jnp.int32)
        off = (i - (tile_ends[e] - tiles[e])) * tile + lane
        ok = off < counts[e]
        a = order[jnp.where(ok, starts[e] + off, 0)]
        tok = a // k
        xt = x[tok]
        up = jax.nn.silu(xt @ wg[e]) * (xt @ wu[e])
        y = (up @ wd[e]).astype(jnp.float32)
        return out.at[tok].add(jnp.where(ok, w_flat[a], 0.0)[:, None] * y)

    out = lax.fori_loop(0, tile_ends[-1], one,
                        jnp.zeros(x.shape, jnp.float32))
    return out, jnp.stack([jnp.sum(counts), jnp.sum(counts > 0)])
