"""The plain reference of the two-mixer block (``perf/configs/
minicpm-sala-l8.json``): straightforward ``jax.numpy`` in float32, the
token-by-token recurrence and a mask a query, no kernels, no cache, no
chunked scan, no page lists, no batching, nothing imported from the
program.

``x [T, E]``; ``RMS(x) = x / sqrt(mean(x^2) + eps) * g``; ``h0 =
scale_emb x E[ids]``; layer ``l``: ``h = h + s Mix_l(RMS(h))``, ``h = h +
s FFN(RMS(h))``, ``s = scale_depth / sqrt(depth)`` with the PUBLISHED
depth; ``FFN(x) = (silu(x Wg) * (x Wu)) Wd``; no bias anywhere; after the
last layer ``RMS``, divided by ``hidden / dim_model_base``, and an untied
head.

- ``lightning-attn``, ``H`` heads of ``D``: ``q = RMS_D(x Wq)``, ``k =
  RMS_D(x Wk)`` a head (one gain of ``D`` each, shared by the heads),
  ``v = x Wv``; rotary on the whole head of q and k (base ``rope_theta``,
  pairs ``(i, i + D / 2)``); ``q`` times ``D ** -0.5``. Head ``n`` (from
  0): ``S_t = lambda_n S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t``,
  ``lambda_n = exp(-2 ** (-8 (n + 1) / H))``, one token after another.
  ``Mix = (sigmoid(x Wgate) * RMS_(H D)(concat o)) Wo``.
- ``minicpm4``, ``H`` query heads over ``Hkv`` K/V heads, no rotary:
  ``q = RMS_D(x Wq)``, ``k = RMS_D(x Wk)``, ``v = x Wv``, scores times
  ``D ** -0.5``, ``Mix = (sigmoid(x Wgate) * a) Wo``. A query at position
  ``t`` with ``t + 1 <= dense_len`` attends every earlier row. Past it:
  compressed key ``j`` of a K/V head is the mean of its ``k[stride j :
  stride j + kernel_size]``, defined once ``stride j + kernel_size - 1 <=
  t``; ``p = softmax_j(q . kc_j x D ** -0.5)`` a query head over the
  defined ``j``, summed over a K/V head's query heads; block ``b`` (rows
  ``block_size b ..``) scores the maximum of that over ``j`` in a window
  of ``block_size / stride + 1`` keys, stride ``block_size / stride``,
  one key of padding in front (the keys that overlap it); block 0 ..
  ``init_blocks - 1`` and the last ``window_size / block_size`` up to the
  query's own are always taken, the rest of ``topk`` by score, ties to
  the lower index; causal softmax over the rows of those blocks.

Departures from the published model are the configuration's ``assumed``.

The weights come rounded to bfloat16 and are upcast one layer at a time;
attention takes its queries in blocks and the FFN its rows, so a request
of 35,840 positions fits beside the weights on one chip. ``precision``
is ``"fp32"`` (``Precision.HIGHEST``, the reference proper) or ``"fp8"``
(the control of ``perf/reference.py:product``: both operands of every
matrix product rounded to float8_e4m3, the nearest precision below the
configuration's bfloat16; the recurrence's own sums stay fp32).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .reference import product

QUERY_BLOCK = 256
ROW_BLOCK = 2048
LINEAR = "lightning-attn"


def rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary(x, positions, base: float):
    """``x [T, H, D]`` rotated by ``positions`` in pairs ``(i, i + D /
    2)``, frequencies ``base ** (-2 i / D)``."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def decays(num_heads: int):
    n = jnp.arange(num_heads, dtype=jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * (n + 1.0) / num_heads)))


def qkv(x, blk, sizes, mm):
    """Heads ``q [T, H, D]``, ``k``/``v [T, Hkv, D]``, q and k normed."""
    t, d = x.shape[0], sizes.head_dim
    heads = lambda w: mm("te,ef->tf", x, w).reshape(t, -1, d)
    return (rms(heads(blk["wq"]), blk["qn"], sizes.eps),
            rms(heads(blk["wk"]), blk["kn"], sizes.eps), heads(blk["wv"]))


def lightning(x, blk, positions, sizes, precision: str):
    """The linear mixer on the normed ``x [T, E]``, one token at a time
    from a zero state."""
    mm = functools.partial(product, precision=precision)
    q, k, v = qkv(x, blk, sizes, mm)
    q = rotary(q, positions, sizes.rope_base) * sizes.head_dim ** -0.5
    k = rotary(k, positions, sizes.rope_base)
    lam = decays(sizes.num_heads)[:, None, None]

    def token(s, row):
        q_t, k_t, v_t = row
        s = lam * s + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    d = sizes.head_dim
    _, o = lax.scan(token, jnp.zeros((sizes.num_heads, d, d), jnp.float32),
                    (q, k, v))
    o = rms(o.reshape(x.shape[0], -1), blk["on"], sizes.eps)
    return mm("tf,fe->te", o * jax.nn.sigmoid(mm("te,ef->tf", x,
                                                 blk["wgate"])), blk["wo"])


def compressed_keys(k, sizes):
    """``k [T, Hkv, D]`` -> ``[J, Hkv, D]``: key ``j`` the mean of rows
    ``stride j .. stride j + kernel_size - 1``, for every ``j`` whose
    rows are all there."""
    n = max((k.shape[0] - sizes.kernel_size) // sizes.kernel_stride + 1, 0)
    at = jnp.arange(n)[:, None] * sizes.kernel_stride \
        + jnp.arange(sizes.kernel_size)
    return k[at].mean(axis=1)


def block_choice(q, kc, positions, blocks: int, sizes, mm):
    """Which blocks each query of ``q [Q, Hkv, G, D]`` at ``positions
    [Q]`` attends past ``dense_len``: a mask ``[Hkv, Q, blocks]``."""
    per = sizes.block_size // sizes.kernel_stride
    j = jnp.arange(kc.shape[0])
    defined = (j * sizes.kernel_stride + sizes.kernel_size - 1)[None, :] \
        <= positions[:, None]                                     # [Q, J]
    s = mm("qhgd,jhd->hgqj", q, kc) * sizes.head_dim ** -0.5
    p = jax.nn.softmax(jnp.where(defined, s, -1e30), axis=-1)
    score = jnp.where(defined, jnp.where(defined, p, 0.0).sum(axis=1),
                      -jnp.inf)                                   # [Hkv, Q, J]
    pooled = lax.reduce_window(
        score, -jnp.inf, lax.max, (1, 1, per + 1), (1, 1, per),
        ((0, 0), (0, 0), (1, per * blocks - kc.shape[0])))        # [.., blocks]
    b = jnp.arange(blocks)
    own = (positions // sizes.block_size)[:, None]                # [Q, 1]
    always = (b < sizes.init_blocks) \
        | (b > own - sizes.window_size // sizes.block_size)
    rank = jnp.where(always, jnp.inf, pooled)
    rank = jnp.where(b <= own, rank, -jnp.inf)                    # not yet there
    best = jnp.argsort(-rank, axis=-1, stable=True)[..., :sizes.topk]
    taken = (best[..., None] == b).any(axis=-2)
    return taken & (b <= own)


def minicpm4(x, blk, positions, sizes, precision: str, chosen=None):
    """The sparse mixer on the normed ``x [T, E]``; a list ``chosen``
    gains the block mask ``[Hkv, T, blocks]`` of every query (all of a
    query's visible blocks up to ``dense_len``)."""
    mm = functools.partial(product, precision=precision)
    t, d = x.shape[0], sizes.head_dim
    q, k, v = qkv(x, blk, sizes, mm)
    hkv = k.shape[1]
    q = q.reshape(t, hkv, -1, d)
    kc = compressed_keys(k, sizes)
    blocks = -(-t // sizes.block_size)
    rows = jnp.arange(t)

    def block(args):
        qb, pb = args
        own = (pb // sizes.block_size)[:, None]                   # [Q, 1]
        taken = jnp.broadcast_to(jnp.arange(blocks) <= own,
                                 (hkv, pb.shape[0], blocks))
        if kc.shape[0] and t > sizes.dense_len:
            taken = jnp.where(
                (pb >= sizes.dense_len)[None, :, None],
                block_choice(qb, kc, pb, blocks, sizes, mm), taken)
        keep = jnp.repeat(taken, sizes.block_size, axis=-1)[..., :t] \
            & (rows[None, :] <= pb[:, None])[None]                # [Hkv, Q, T]
        s = mm("qhgd,khd->hgqk", qb, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
        return mm("hgqk,khd->qhgd", p, v), taken

    n = math.gcd(t, QUERY_BLOCK)
    a, taken = lax.map(block, (q.reshape(t // n, n, *q.shape[1:]),
                               positions.reshape(t // n, n)))
    if chosen is not None:
        chosen.append(taken.transpose(1, 0, 2, 3).reshape(hkv, t, blocks))
    a = a.reshape(t, -1) * jax.nn.sigmoid(mm("te,ef->tf", x, blk["wgate"]))
    return mm("tf,fe->te", a, blk["wo"])


def ffn(x, blk, mm):
    """Gated SiLU, the rows in blocks."""
    def rows(xb):
        return mm("tf,fe->te", jax.nn.silu(mm("te,ef->tf", xb, blk["wg"]))
                  * mm("te,ef->tf", xb, blk["wu"]), blk["wd"])

    n = math.gcd(x.shape[0], ROW_BLOCK)
    return lax.map(rows, x.reshape(-1, n, x.shape[1])).reshape(x.shape)


def layer(h, blk, i: int, positions, sizes, precision: str, chosen=None):
    blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
    mm = functools.partial(product, precision=precision)
    x = rms(h, blk["ln1"], sizes.eps)
    mix = lightning(x, blk, positions, sizes, precision) \
        if sizes.mixers[i] == LINEAR \
        else minicpm4(x, blk, positions, sizes, precision, chosen)
    h = h + sizes.residual_scale * mix
    return h + sizes.residual_scale * ffn(rms(h, blk["ln2"], sizes.eps), blk,
                                          mm)


def hidden(weights, tokens, sizes, precision: str, chosen=None):
    """The head's input ``[T, E]`` of one sequence ``tokens [T]``."""
    positions = jnp.arange(tokens.shape[0])
    h = sizes.scale_emb * weights["embed"][tokens].astype(jnp.float32)
    for i, blk in enumerate(weights["blocks"]):
        h = layer(h, blk, i, positions, sizes, precision, chosen)
    return rms(h, weights["lnf"].astype(jnp.float32), sizes.eps) \
        * sizes.logit_scale


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def served_logits(weights, tokens, at, *, sizes, precision: str):
    """Logits ``[N, V]`` at positions ``at [N]`` of one sequence ``tokens
    [T]`` (padded at its end: every mixer is causal)."""
    h = hidden(weights, tokens, sizes, precision)
    return product("ne,ev->nv", h[at], weights["head"].astype(jnp.float32),
                   precision)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def all_logits(weights, tokens, *, sizes, precision: str = "fp32"):
    """Logits ``[T, V]`` at every position: the tests' full forward."""
    h = hidden(weights, tokens, sizes, precision)
    return product("te,ev->tv", h, weights["head"].astype(jnp.float32),
                   precision)


def leaf_norms(weights) -> dict:
    """L2 norm of every leaf, the tree's own shape: what two makings of
    one seed's weights are compared by."""
    return jax.tree.map(
        lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))),
        weights)


@functools.partial(jax.jit, static_argnames=("sizes",))
def attended_blocks(weights, tokens, *, sizes):
    """The blocks every query attends in each sparse layer, ``[sparse
    layers, Hkv, T, blocks]`` (all of its visible ones up to
    ``dense_len``): what a program's selector is held to."""
    chosen = []
    hidden(weights, tokens, sizes, "fp32", chosen)
    return jnp.stack(chosen)
