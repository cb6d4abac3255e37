"""Fused flash attention — the Pallas path for the LM's hot op.

``models.transformer.full_attention`` (via ``parallel.ring``) materializes
the whole ``[B, H, T, T]`` fp32 score matrix per layer; at long context
that is the dominant HBM cost (T=4096, H=8, B=2 ⇒ ~1 GB per layer just
for scores). This module routes the local attention computation through
the TPU flash-attention Pallas kernel bundled with JAX
(``jax.experimental.pallas.ops.tpu.flash_attention`` — tiled online
softmax, O(T * block) score memory, custom_vjp so training works), the
same selected-on-TPU pattern as the fused Adam kernel
(``ops/pallas_adam.py``).

Off-TPU the kernel cannot lower (Mosaic is TPU-only), so the wrapper
falls back to the kernel's own pure-JAX reference twin
(``mha_reference_no_custom_vjp`` — same math, autodiff gradients): the
CPU test mesh exercises every caller's plumbing, and tests pin the
fallback against the repo oracle (``ring.full_attention``) fwd+grad.

Where it plugs in (``strategies.seq.SeqConfig.attn_impl = "flash"``):
- scheme ``full``: directly — the whole-sequence kernel.
- scheme ``ulysses``: as the local kernel after the all_to_all head
  re-partition (each device computes full-sequence attention over its
  head subset — exactly the kernel's shape).
- scheme ``ring``: NOT available — the ring's streaming-softmax state
  (m, l, acc) must cross ``ppermute`` steps, which the bundled kernel
  does not expose; the ring keeps its hand-rolled blockwise update.
"""

from __future__ import annotations

import math

import jax

from jax.experimental.pallas.ops.tpu import flash_attention as _fa

# The bundled kernel's default q/k block (``BlockSizes.get_default``): on
# TPU it refuses a sequence that is not a whole number of blocks
# ("block_q=128 should be smaller or equal to q_seq_len", "kv_seq_len
# should be divisible by block_k_major"). ``SeqConfig.validate_topology``
# rejects such a config up front; the reference twin has no such limit.
FLASH_BLOCK = 128


def flash_attention_bthd(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: float | None = None, platform: str | None = None,
) -> jax.Array:
    """Flash attention over ``[B, T, H, D]`` (the model's layout; the
    kernel wants ``[B, H, T, D]`` — transposed in and out). Causality is
    from position 0 (aligned q/k — the full/ulysses cases); there is no
    offset support, so this cannot serve as the ring's travelling-block
    kernel. On TPU, T must be a multiple of :data:`FLASH_BLOCK`.

    ``platform`` is the platform of the devices the computation will run
    on (``mesh.devices.flat[0].platform`` for a mesh program — what
    ``strategies.seq`` passes); kernel selection happens at trace time,
    when placement is not introspectable, so callers placing the program
    on a non-default backend must say so. ``None`` falls back to
    ``jax.default_backend()`` (round-4 advisor)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if platform is None:
        platform = jax.default_backend()
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if platform == "tpu":
        out = _fa.flash_attention(qt, kt, vt, causal=causal, sm_scale=scale)
    else:
        # fp32 score accumulation like both the TPU kernel and the repo's
        # einsum path (ring.full_attention upcasts scores) — the bf16
        # reference would otherwise accumulate the softmax in ~3
        # significant digits and drift from the TPU run at long T.
        out = _fa.mha_reference_no_custom_vjp(
            qt.astype(jax.numpy.float32), kt.astype(jax.numpy.float32),
            vt.astype(jax.numpy.float32), None, causal=causal,
            sm_scale=scale,
        ).astype(q.dtype)
    return out.transpose(0, 2, 1, 3)
