"""Fused flash attention — the Pallas path for the LM's hot op.

``models.transformer.full_attention`` (via ``parallel.ring``) materializes
the whole ``[B, H, T, T]`` fp32 score matrix per layer; at long context
that is the dominant HBM cost (T=4096, H=8, B=2 ⇒ ~1 GB per layer just
for scores). This module routes the local attention computation through
the TPU flash-attention Pallas kernel bundled with JAX
(``jax.experimental.pallas.ops.tpu.flash_attention`` — tiled online
softmax, O(T * block) score memory, custom_vjp so training works), the
same selected-on-TPU pattern as the fused Adam kernel
(``ops/pallas_adam.py``). The kernel's own default is 128 for every block
whatever the shape, 32,768 grid steps a forward call at the benchmark's
training shape and 3.8% of its roofline; :func:`flash_block_sizes` fits
the blocks to ``T`` and ``D`` instead (PERF.md section 6, PR 27).

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

# The granularity of ``T`` on TPU: every block of the bundled kernel is a
# multiple of 128 lanes, and it refuses a sequence that is not a whole
# number of them ("block_q=128 should be smaller or equal to q_seq_len",
# "kv_seq_len should be divisible by block_k_major").
# ``SeqConfig.validate_topology`` rejects such a config up front; the
# reference twin has no such limit. The blocks themselves are fitted to
# the shape by :func:`flash_block_sizes`.
FLASH_BLOCK = 128

# The widest block each field of the bundled kernels' ``BlockSizes`` is
# given, and the major block a minor one has to divide (``None``: the
# sequence), from a sweep on one v5e at [8, 16, 2048, 64] bf16, causal
# (PERF.md section 6, PR 27). 2048-wide tiles are slower where VMEM admits
# them at all. dQ's k-major stays at 512 because the bundled wrapper
# broadcasts ``di`` to ``[B, H, T, block_k_major_dq]`` fp32 in HBM before
# the kernel: at 1024 that copy costs three times what the kernel gains.
_BLOCK_CAPS = {  # a major block before its minors
    "block_q": (1024, None),
    "block_k_major": (1024, None),
    "block_k": (1024, "block_k_major"),
    "block_q_major_dkv": (1024, None),
    "block_k_major_dkv": (1024, None),
    "block_k_dkv": (1024, "block_k_major_dkv"),
    "block_q_dkv": (512, "block_q_major_dkv"),
    "block_q_dq": (1024, None),
    "block_k_major_dq": (512, None),
    "block_k_dq": (512, "block_k_major_dq"),
}


def _fit(n: int, cap: int) -> int:
    """The largest multiple of :data:`FLASH_BLOCK` that divides ``n`` and
    is at most ``cap``; 128 where none does, which the kernel refuses."""
    return max((b for b in range(FLASH_BLOCK, min(n, cap) + 1, FLASH_BLOCK)
                if n % b == 0), default=FLASH_BLOCK)


def flash_block_sizes(seq_len: int, head_dim: int) -> _fa.BlockSizes:
    """The bundled kernels' block sizes for ``[B, H, seq_len, head_dim]``:
    each major block the widest divisor of ``seq_len`` under its cap,
    each minor block the widest divisor of its major. The caps fit a
    v5e's VMEM up to ``head_dim`` 256; at 512 its compiler refuses the
    dK/dV kernel at T 8192, so beyond 256 the caps halve with each
    doubling (``tests/test_chip_compile.py`` compiles that shape).
    ``block_b`` stays 1: the choice sees no batch, and 2 bought 1.7%."""
    shrink = -(-head_dim // 256)
    blocks: dict[str, int] = {}
    for name, (cap, major) in _BLOCK_CAPS.items():
        blocks[name] = _fit(blocks[major] if major else seq_len,
                            cap // shrink)
    return _fa.BlockSizes(block_b=1, **blocks)


def flash_attention_bthd(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: float | None = None, platform: str | None = None,
) -> jax.Array:
    """Flash attention over ``[B, T, H, D]`` (the model's layout; the
    kernel wants ``[B, H, T, D]`` — transposed in and out). Causality is
    from position 0 (aligned q/k — the full/ulysses cases); there is no
    offset support, so this cannot serve as the ring's travelling-block
    kernel. On TPU, T must be a multiple of :data:`FLASH_BLOCK`, and the
    kernels' blocks are :func:`flash_block_sizes` of ``(T, D)``.

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
        out = _fa.flash_attention(
            qt, kt, vt, causal=causal, sm_scale=scale,
            block_sizes=flash_block_sizes(q.shape[1], q.shape[3]))
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
