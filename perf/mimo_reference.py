"""The plain reference of the window/global routed-expert decoder
(``perf/configs/mimo-v2-flash-ep16.json``): straightforward
``jax.numpy`` in float32, no kernels, no cache, no batching, nothing
imported from the program.

``x [T, E]``; ``RMS(x) = x / sqrt(mean(x^2) + eps) * g``; layer ``l``:
``h = x + Attn_l(RMS(x))``, ``y = h + FFN_l(RMS(h))``; no bias anywhere;
after the last layer ``RMS`` and an untied head.

- Attention, by the layer's kind (0 global, 1 window): ``q = x Wq -> [T,
  Hq, 192]``, ``k = x Wk -> [T, Hkv, 192]``, ``v = value_scale * (x Wv)
  -> [T, Hkv, 128]``; rotary on the first ``rotary_dim`` dimensions of
  each Q and K head, pairs ``(i, i + rotary_dim / 2)``, the base by kind;
  query head ``h`` reads K/V head ``h // (Hq / Hkv)``; ``s_ij = q_i . k_j
  / sqrt(192)`` over ``j <= i`` (global) or ``i - window < j <= i``
  (window). A window layer adds one learned scalar ``b_h`` a query head
  to the softmax's denominator only.
- FFN: dense gated SiLU ``(silu(x Wg) * (x Wu)) Wd``, or routed experts
  of the same form: ``sc = sigmoid(x Wr)`` in fp32 over all
  ``router_width`` experts, the ``top_k`` of ``sc + c``, weights ``sc_e /
  sum of the chosen sc``, ``out = sum over chosen e of w_e Expert_e(x)``.
  The reference is given the same share as the program: it sums over the
  experts ``experts_held`` alone, every one of them over every token with
  a weight of zero where the token did not choose it.

Departures from the published model, each under ``assumed`` in the
configuration's file: the value scale multiplies V; the rotary pairing
and its 64 dimensions; the window counts the query's own position; the
multi-token-prediction layers are left out; random weights.

The weights come rounded to bfloat16 and are upcast one layer at a time;
attention takes its queries in blocks, so a request of 9,216 positions
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
WINDOW, MOE = 1, 1


def rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary(x, positions, base: float, rotary_dim: int):
    """``x [T, H, D]``: rotate pairs ``(i, i + rotary_dim / 2)`` of the
    first ``rotary_dim`` dimensions by ``positions * base ** (-2 i /
    rotary_dim)``."""
    half = rotary_dim // 2
    freqs = base ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary_dim)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(q, k, v, positions, window, sink, precision: str):
    """``q [T, Hq, D]``, ``k [T, Hkv, D]``, ``v [T, Hkv, Dv]`` -> ``[T,
    Hq, Dv]``; the queries in blocks of at most ``QUERY_BLOCK``."""
    t, hq, d = q.shape
    group = hq // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    mm = functools.partial(product, precision=precision)

    def block(args):
        qb, pb = args
        s = mm("qhd,khd->hqk", qb, k) / math.sqrt(d)
        keep = positions[None, :] <= pb[:, None]
        if window is not None:
            keep &= positions[None, :] > pb[:, None] - window
        s = jnp.where(keep[None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[:, None, None])
        e = jnp.exp(s - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(sink[:, None, None] - m)
        return mm("hqk,khd->qhd", e / den, v)

    n = math.gcd(t, QUERY_BLOCK)
    out = lax.map(block, (q.reshape(t // n, n, hq, d),
                          positions.reshape(t // n, n)))
    return out.reshape(t, hq, -1)


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
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed(x, blk, sizes, mm):
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
    kind = sizes.layer_kinds[i]
    mm = functools.partial(product, precision=precision)
    t = h.shape[0]
    x = rms(h, blk["ln1"], sizes.eps)
    rot = functools.partial(rotary, positions=positions,
                            base=sizes.rope_base[kind],
                            rotary_dim=sizes.rotary_dim)
    q = rot(mm("te,ef->tf", x, blk["wq"]).reshape(t, -1, sizes.head_dim))
    k = rot(mm("te,ef->tf", x, blk["wk"]).reshape(t, -1, sizes.head_dim))
    v = sizes.value_scale * mm("te,ef->tf", x, blk["wv"]).reshape(
        t, -1, sizes.v_head_dim)
    a = attention(q, k, v, positions,
                  sizes.window if kind == WINDOW else None,
                  blk.get("sink"), precision)
    h = h + mm("tf,fe->te", a.reshape(t, -1), blk["wo"])
    x = rms(h, blk["ln2"], sizes.eps)
    if sizes.ffn_kinds[i] == MOE:
        if chosen is not None:
            chosen.append(choose(x, blk, sizes)[0])
        return h + routed(x, blk, sizes, mm)
    return h + gated(x, blk["wg"], blk["wu"], blk["wd"], mm)


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
