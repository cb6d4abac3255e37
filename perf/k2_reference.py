"""The plain reference of the latent-attention routed-expert block
(``perf/configs/kimi-k2-ep32.json``): straightforward ``jax.numpy`` in
float32, the PUBLISHED (up-projected) form of the attention only, no
kernels, no cache, no absorbed form, no batching, nothing imported from
the program.

``x [T, E]``; ``RMS(x) = x / sqrt(mean(x^2) + eps) * g``; layer ``l``:
``h = x + MLA_l(RMS(x))``, ``y = h + FFN_l(RMS(h))``; no bias anywhere;
after the last layer ``RMS`` and an untied head.

- MLA, a token ``x`` at position ``p``: ``c_q = RMS(x W_qa)`` (its own
  gain); ``q = c_q W_qb -> [H, nope + rope] = [q_n | q_r]``; ``[c | k_r]
  = x W_kva``; ``c = RMS(c)`` (its own gain); ``k_r = rot(k_r, p)``, ONE
  a token, shared by every head; ``q_r = rot(q_r, p)``; ``[k_n,h | v_h]
  = c W_kvb`` a head. ``s_h(p, j) = scale (q_n,h(p) . k_n,h(j) + q_r,h(p)
  . k_r(j))`` over ``j <= p``; softmax in fp32; ``o_h = sum_j P v_h(j)``;
  output ``concat_h(o_h) W_o``. ``scale = (nope + rope) ** -0.5 x m ** 2``
  with ``m = 0.1 x mscale_all_dim x ln(factor) + 1``.
- Rotary (YaRN) on the ``rope`` dimensions: ``theta_i = base ** (-2 i /
  rope)``; ``d(beta) = rope ln(original / (2 pi beta)) / (2 ln base)``;
  ``low = floor(d(beta_fast))``, ``high = ceil(d(beta_slow))`` (inside
  ``[0, rope - 1]``); ``r_i = clip((i - low) / (high - low), 0, 1)``;
  frequency ``theta_i (1 - r_i) + theta_i / factor x r_i``; cos and sin
  times ``m(mscale) / m(mscale_all_dim)``. Pairs ``(i, i + rope / 2)``.
- FFN: dense gated SiLU ``(silu(x Wg) * (x Wu)) Wd``, or ``sc =
  sigmoid(x Wr)`` in fp32 over all ``router_width`` experts, the
  ``top_k`` of ``sc + c``, weights ``route_scale x sc_e / (sum of the
  chosen sc + 1e-20)``, ``out = sum over chosen e of w_e Expert_e(x) +
  Shared(x)``. The reference is given the program's share: it sums over
  the experts ``experts_held`` alone, and computes the shared expert
  whole.

Departures from the published model, each under ``assumed`` in the
configuration's file: the rotary pairing; random weights.

The weights come rounded to bfloat16 and are upcast one layer at a time;
attention takes its queries in blocks, so a request of 17,408 positions
fits beside the weights on one chip. ``precision`` is ``"fp32"``
(``Precision.HIGHEST``, the reference proper) or ``"fp8"`` (the control
of ``perf/reference.py:product``: both operands of every matrix product
rounded to float8_e4m3, the nearest precision below the configuration's
bfloat16). The router's product stays in fp32 in both: the configuration
states it so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .reference import product

QUERY_BLOCK = 256
MOE = 1


def rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(sizes):
    """``rope_dim / 2`` frequencies; ``(low, high)`` beside them for the
    tests."""
    dim, base = sizes.rope_dim, sizes.rope_base
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    theta = base ** (-2.0 * i / dim)
    if sizes.rope_factor <= 1:
        return theta, (None, None)

    def turn(beta):
        return dim * math.log(sizes.rope_original / (2 * math.pi * beta)) \
            / (2 * math.log(base))

    low = max(math.floor(turn(sizes.beta_fast)), 0)
    high = min(math.ceil(turn(sizes.beta_slow)), dim - 1)
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return theta * (1 - r) + theta / sizes.rope_factor * r, (low, high)


def rotary(x, positions, sizes):
    """``x [T, H, rope_dim]`` rotated by ``positions`` in pairs ``(i, i +
    rope_dim / 2)``."""
    half = sizes.rope_dim // 2
    freqs, _ = yarn_frequencies(sizes)
    amp = yarn_m(sizes.rope_factor, sizes.mscale) \
        / yarn_m(sizes.rope_factor, sizes.mscale_all_dim)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = amp * jnp.cos(ang), amp * jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def score_scale(sizes) -> float:
    m = yarn_m(sizes.rope_factor, sizes.mscale_all_dim)
    return (sizes.nope_dim + sizes.rope_dim) ** -0.5 * m * m


def attention(q, k, v, positions, scale: float, precision: str):
    """``q``/``k [T, H, D]``, ``v [T, H, Dv]`` -> ``[T, H, Dv]``, causal;
    the queries in blocks of at most ``QUERY_BLOCK``."""
    t, h, d = q.shape
    mm = functools.partial(product, precision=precision)

    def block(args):
        qb, pb = args
        s = mm("qhd,khd->hqk", qb, k) * scale
        keep = positions[None, :] <= pb[:, None]
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", p, v)

    n = math.gcd(t, QUERY_BLOCK)
    out = lax.map(block, (q.reshape(t // n, n, h, d),
                          positions.reshape(t // n, n)))
    return out.reshape(t, h, -1)


def mla(x, blk, positions, sizes, precision: str):
    """The latent attention of one layer on the normed ``x [T, E]``."""
    mm = functools.partial(product, precision=precision)
    t, h = x.shape[0], sizes.num_heads
    c_q = rms(mm("te,ef->tf", x, blk["wqa"]), blk["qn"], sizes.eps)
    row = mm("te,ef->tf", x, blk["wkva"])
    c = rms(row[:, :sizes.kv_lora], blk["kvn"], sizes.eps)
    k_r = row[:, None, sizes.kv_lora:]
    q = mm("tf,fg->tg", c_q, blk["wqb"]).reshape(t, h, -1)
    q = jnp.concatenate([q[..., :sizes.nope_dim],
                         rotary(q[..., sizes.nope_dim:], positions, sizes)],
                        -1)
    kv = mm("tc,cg->tg", c, blk["wkvb"]).reshape(t, h, -1)
    k = jnp.concatenate([kv[..., :sizes.nope_dim], jnp.broadcast_to(
        rotary(k_r, positions, sizes), (t, h, sizes.rope_dim))], -1)
    a = attention(q, k, kv[..., sizes.nope_dim:], positions,
                  score_scale(sizes), precision)
    return mm("tf,fe->te", a.reshape(t, -1), blk["wo"])


def gated(x, wg, wu, wd, mm):
    return mm("tf,fe->te", jax.nn.silu(mm("te,ef->tf", x, wg))
              * mm("te,ef->tf", x, wu), wd)


def choose(x, blk, sizes):
    """The router: ``(chosen [T, top_k]`` of all the published experts,
    ``weights [T, top_k])``."""
    sc = jax.nn.sigmoid(jnp.einsum("te,en->tn", x, blk["wr"],
                                   precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(sc + blk["rc"], sizes.top_k)
    picked = jnp.take_along_axis(sc, chosen, axis=-1)
    return chosen, sizes.route_scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def routed(x, blk, sizes, mm):
    """The held experts' part of the routed sum (no shared expert)."""
    chosen, weights = choose(x, blk, sizes)
    out = jnp.zeros_like(x)
    for i in range(sizes.held):
        w = jnp.sum(jnp.where(chosen == sizes.experts_held[0] + i,
                              weights, 0.0), axis=-1)
        out = out + w[:, None] * gated(x, blk["eg"][i], blk["eu"][i],
                                       blk["ed"][i], mm)
    return out


def layer(h, blk, i: int, positions, sizes, precision: str, chosen=None):
    """One layer on ``h [T, E]``; a list ``chosen`` gains the routed
    layer's choices ``[T, top_k]``."""
    blk = jax.tree.map(lambda a: a.astype(jnp.float32), blk)
    mm = functools.partial(product, precision=precision)
    h = h + mla(rms(h, blk["ln1"], sizes.eps), blk, positions, sizes,
                precision)
    x = rms(h, blk["ln2"], sizes.eps)
    if sizes.ffn_kinds[i] != MOE:
        return h + gated(x, blk["wg"], blk["wu"], blk["wd"], mm)
    if chosen is not None:
        chosen.append(choose(x, blk, sizes)[0])
    return h + routed(x, blk, sizes, mm) \
        + gated(x, blk["sg"], blk["su"], blk["sd"], mm)


def hidden(weights, tokens, sizes, precision: str, chosen=None):
    """Final-norm output ``[T, E]`` of one sequence ``tokens [T]``."""
    positions = jnp.arange(tokens.shape[0])
    h = weights["embed"][tokens].astype(jnp.float32)
    for i, blk in enumerate(weights["blocks"]):
        h = layer(h, blk, i, positions, sizes, precision, chosen)
    return rms(h, weights["lnf"].astype(jnp.float32), sizes.eps)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def served_logits(weights, tokens, at, *, sizes, precision: str):
    """Logits ``[N, V]`` at positions ``at [N]`` of one sequence ``tokens
    [T]`` (padded at its end: attention is causal)."""
    h = hidden(weights, tokens, sizes, precision)
    return product("ne,ev->nv", h[at], weights["head"].astype(jnp.float32),
                   precision)


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def all_logits(weights, tokens, *, sizes, precision: str = "fp32"):
    """Logits ``[T, V]`` at every position: the tests' full forward."""
    h = hidden(weights, tokens, sizes, precision)
    return product("te,ev->tv", h, weights["head"].astype(jnp.float32),
                   precision)


@functools.partial(jax.jit, static_argnames=("sizes",))
def routed_choices(weights, tokens, *, sizes):
    """What each routed layer's router picks at every position of one
    sequence, ``[routed layers, T, top_k]`` of all the published experts:
    the count a program's routing counters are held to."""
    chosen = []
    hidden(weights, tokens, sizes, "fp32", chosen)
    return jnp.stack(chosen)
