"""The plain reference: the decoder of ``perf/configs/*`` in float32.

Straightforward ``jax.numpy``: no kernels, no cache, no batching of
requests, no sharding, and nothing imported from the program. It
follows the block the configurations state under ``assumed``: pre-LN,
sequential residual, rotary on whole heads (interleaved pairs), causal
softmax attention scaled by 1/sqrt(head_dim), tanh-gelu MLP with biases,
final LayerNorm, untied head, LayerNorm eps 1e-6; loss = weighted mean
cross-entropy of fp32 logits; TF1-style Adam (eps outside the square
root, bias correction folded into the step size).

``precision`` selects how every matrix product is computed:

- ``"fp32"``: float32 at ``Precision.HIGHEST``, the reference proper;
- ``"fp8"``: both operands rounded to float8_e4m3 with one scale per
  tensor (straight-through in the backward pass), product in bfloat16
  with float32 accumulation. This is the control: the nearest precision
  below the bfloat16 that the cells' configurations state.

Blocks are stacked on a leading layer axis and scanned, each under
``jax.checkpoint``, and a batch is taken in blocks of rows, so the
timed sizes fit beside nothing else on one chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-6
F8_MAX = 448.0
ADAM = dict(b1=0.9, b2=0.999, eps=1e-8)


def _to_fp8(x):
    scale = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)  # straight-through


def product(eq: str, a, b, precision: str):
    if precision == "fp32":
        return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        a, b = (_to_fp8(x.astype(jnp.float32)).astype(jnp.bfloat16)
                for x in (a, b))
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def layernorm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * g + b


def rotary(x, positions, base: float):
    """``x [B, T, H, D]``, ``positions [T]``: rotate pairs (2i, 2i+1) by
    ``positions * base**(-2i/D)``."""
    d = x.shape[-1]
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs  # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(h, blk, positions, sizes, precision: str):
    b, t, _ = h.shape
    heads = lambda a: a.reshape(b, t, sizes.num_heads, sizes.head_dim)
    mm = functools.partial(product, precision=precision)
    x = layernorm(h, blk["ln1_g"], blk["ln1_b"])
    q = rotary(heads(mm("bte,ef->btf", x, blk["wq"])), positions,
               sizes.rope_base)
    k = rotary(heads(mm("bte,ef->btf", x, blk["wk"])), positions,
               sizes.rope_base)
    v = heads(mm("bte,ef->btf", x, blk["wv"]))
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(sizes.head_dim)
    causal = positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = mm("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1)
    h = h + mm("bte,ef->btf", a, blk["wo"])
    x = layernorm(h, blk["ln2_g"], blk["ln2_b"])
    up = gelu_tanh(mm("bte,ef->btf", x, blk["w1"]) + blk["b1"])
    return h + mm("btf,fe->bte", up, blk["w2"]) + blk["b2"]


def hidden(weights, tokens, sizes, precision: str):
    """Final-LayerNorm output ``[B, T, E]`` for tokens ``[B, T]``."""
    positions = jnp.arange(tokens.shape[1])

    @jax.checkpoint
    def body(h, blk):
        return block(h, blk, positions, sizes, precision), None

    h, _ = lax.scan(body, weights["embed"][tokens], weights["blocks"])
    return layernorm(h, weights["lnf_g"], weights["lnf_b"])


def loss_sum(weights, tokens, targets, scored, sizes, precision: str):
    """Sum of the scored tokens' cross-entropy (the caller divides)."""
    h = hidden(weights, tokens, sizes, precision)
    logits = product("bte,ev->btv", h, weights["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(ce * scored)


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, the stacked blocks layer by layer, as
    ``{"embed": x, "blocks": {"wq": [L], ...}, ...}``."""
    norm = lambda a, axes: jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))
    out = {k: norm(v, None) for k, v in tree.items() if k != "blocks"}
    out["blocks"] = {k: norm(v, tuple(range(1, v.ndim)))
                     for k, v in tree["blocks"].items()}
    return out


@functools.partial(jax.jit, static_argnames=("sizes", "precision", "rows",
                                              "lr"), donate_argnums=(0, 1, 2))
def train_step(weights, m, v, step, tokens, targets, scored, *, sizes,
               precision: str, rows: int, lr: float):
    """One step on batch ``[B, T]`` taken ``rows`` rows at a time:
    ``(weights, m, v, step, loss, gradient)``."""
    b, t = tokens.shape
    total = jnp.sum(scored)
    split = lambda a: a.reshape(b // rows, rows, t)

    def one(acc, xs):
        val, g = jax.value_and_grad(
            lambda w: loss_sum(w, *xs, sizes, precision) / total)(weights)
        return (acc[0] + val, jax.tree.map(jnp.add, acc[1], g)), None

    zero = jax.tree.map(jnp.zeros_like, weights)
    (loss, grads), _ = lax.scan(
        one, (jnp.float32(0), zero),
        (split(tokens), split(targets), split(scored)))
    step = step + 1
    tf = step.astype(jnp.float32)
    lr_t = lr * jnp.sqrt(1.0 - ADAM["b2"] ** tf) / (1.0 - ADAM["b1"] ** tf)
    m = jax.tree.map(lambda m, g: ADAM["b1"] * m + (1 - ADAM["b1"]) * g,
                     m, grads)
    v = jax.tree.map(lambda v, g: ADAM["b2"] * v + (1 - ADAM["b2"]) * g * g,
                     v, grads)
    weights = jax.tree.map(
        lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + ADAM["eps"]),
        weights, m, v)
    return weights, m, v, step, loss, grads


@functools.partial(jax.jit, static_argnames=("sizes", "precision"))
def served_logits(weights, tokens, at, *, sizes, precision: str):
    """Logits ``[N, V]`` at positions ``at [N]`` of one sequence
    ``tokens [T]`` (padded at its end: attention is causal)."""
    h = hidden(weights, tokens[None, :], sizes, precision)[0]
    return product("ne,ev->nv", h[at], weights["head"], precision)
