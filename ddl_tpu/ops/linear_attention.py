"""Linear attention with a decay a head: the recurrence a layer of this
kind keeps a state for, written twice over the same arithmetic.

A head ``n`` of ``H`` keeps ``S [Dk, Dv]`` in fp32: ``S_t = lambda_n
S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t``, ``lambda_n = exp(-rate_n)``,
``rate_n = 2 ** (-8 (n + 1) / H)`` (:func:`decay_rates`). What a token
costs does not grow with the context, and what a sequence keeps is the
state alone.

- :func:`step`: one token a slot (a decode tick), the recurrence as
  written, elementwise in fp32; a slot that is not active keeps its
  state.
- :func:`scan_chunks`: a block of ``T`` tokens of one sequence (a
  prefill, whole or one chunk of it) from the state before it. Within a
  chunk of ``CHUNK`` tokens the decayed causal product ``(q k^T * D) v``,
  ``D[i, s] = lambda ** (i - s)`` for ``s <= i``; between chunks the
  state. ``D`` is built from differences, never as ``lambda ** i x
  lambda ** -s``: the fastest head's ``lambda ** -256`` is past fp32.
  Padding behind the block's real rows changes neither the state nor a
  real row's output.

Both give what the token-by-token recurrence gives, to rounding; products
with the fp32 state are taken at the highest precision (a sixth of a
percent of a token's FLOPs), so the state is never rounded to bf16 on
its way through the MXU.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

CHUNK = 256
_HI = lax.Precision.HIGHEST


def decay_rates(num_heads: int):
    """``rate_n = 2 ** (-8 (n + 1) / H)``, fp32 ``[H]``: the slopes of
    Lightning Attention-2; a head decays by ``exp(-rate_n)`` a token."""
    n = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * n / num_heads)


def step(state, q, k, v, rates, active):
    """One token a slot: ``state [B, H, Dk, Dv]`` fp32, ``q``/``k [B, H,
    Dk]``, ``v [B, H, Dv]``, ``active [B]`` -> ``(o [B, H, Dv]`` in
    ``v``'s dtype, ``state)``."""
    f32 = jnp.float32
    lam = jnp.exp(-rates)[None, :, None, None]
    new = lam * state + k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :]
    new = jnp.where(active[:, None, None, None], new, state)
    o = jnp.sum(q.astype(f32)[..., :, None] * new, axis=-2)
    return o.astype(v.dtype), new


def scan_chunks(state, q, k, v, rates, length, chunk: int = CHUNK):
    """A block of one sequence: ``q``/``k [T, H, Dk]``, ``v [T, H, Dv]``,
    the first ``length`` rows real, from ``state [H, Dk, Dv]`` (fp32) ->
    ``(o [T, H, Dv]`` in ``v``'s dtype, the state after row ``length -
    1)``."""
    t, h, _ = q.shape
    c = math.gcd(t, chunk)
    f32 = jnp.float32
    i = jnp.arange(c, dtype=f32)
    rate = rates[:, None, None]                               # [H, 1, 1]
    gap = i[:, None] - i[None, :]                             # i - s
    decay = jnp.where(gap >= 0, jnp.exp(-rate * jnp.maximum(gap, 0.0)), 0.0)
    into = jnp.exp(-rates[None, :] * (i[:, None] + 1.0))      # [C, H]

    def one(s, xs):
        qc, kc, vc, first = xs
        n = jnp.clip(length - first, 0, c).astype(f32)         # real rows
        a = jnp.einsum("ihd,shd->his", qc, kc,
                       preferred_element_type=f32) * decay
        o = jnp.einsum("his,she->ihe", a.astype(vc.dtype), vc,
                       preferred_element_type=f32)
        o = o + into[..., None] * jnp.einsum(
            "ihd,hde->ihe", qc.astype(f32), s, precision=_HI)
        # what each real row still weighs once the chunk's last has passed
        left = (n - 1.0 - i)[:, None]
        w = jnp.where(left >= 0,
                      jnp.exp(-rates[None, :] * jnp.maximum(left, 0.0)),
                      0.0)                                     # [C, H]
        s = jnp.exp(-rates * n)[:, None, None] * s + jnp.einsum(
            "shd,she->hde", kc.astype(f32) * w[..., None], vc.astype(f32),
            precision=_HI)
        return s, o.astype(vc.dtype)

    blocks = lambda a: a.reshape(t // c, c, *a.shape[1:])
    state, o = lax.scan(one, state, (blocks(q), blocks(k), blocks(v),
                                     jnp.arange(0, t, c, dtype=jnp.int32)))
    return o.reshape(t, h, -1), state
