"""Analytic FLOPs accounting and MFU (ISSUE 10 tentpole piece 2).

Model-FLOPs utilization — achieved FLOP/s over the hardware's peak — is
the efficiency headline of the pjit/TPUv4 LM-scaling work (PAPERS.md
2204.06514 reports MFU, not tok/s, precisely because it composes across
model sizes and chip generations). This module makes the numerator
EXACT and ANALYTIC: closed-form matmul FLOPs per train step / serve
token, parameterized on the same config dataclasses the programs
compile from, so the ``train_mfu`` gauge is a derived quantity of
(config, measured span time, device peak) and nothing else.

Accounting conventions (the standard ones, stated so the hand-computed
test oracle and this module can only disagree by a real bug):

- A matmul ``[m, k] @ [k, n]`` costs ``2*m*k*n`` FLOPs (multiply +
  accumulate). Only matmul-shaped work is counted — layernorms,
  softmax, bias adds, pooling and activations are O(elements) noise
  next to the contractions on both model families here.
- Attention computes the FULL ``T x T`` score matrix (that is what the
  einsum kernels here materialize — causal masking discards half the
  result but not the work), so forward attention per layer is
  ``4*B*T*T*e`` (QK^T plus AV).
- Backward is the standard 2x forward (each matmul re-appears as a
  dL/dx and a dL/dW matmul); a train step is ``3x`` forward.
  ``remat=True`` recomputes each block's forward in the backward pass:
  ``+1x`` the BLOCK forward (head/embed are not rematerialized).
- **Mode-awareness** (pp/tp/zero1): the parallel modes re-shard the
  SAME math — total model FLOPs per step are topology-invariant
  (tensor parallelism splits the contractions, pipelining splits the
  layers, ZeRO shards the optimizer; none adds or removes a matmul).
  What changes is the denominator: :func:`mfu` divides by
  ``n_devices * peak``, and the trainers pass their mesh size, so a
  pp=2 run at the same step time reports half the MFU of a 1-chip run
  — the bubble made visible, not hidden.
- Serving is accounted PER TOKEN, and **paged-aware**: decode attention
  cost is ``4*e*W`` per layer where ``W`` is the attended width — the
  page-count-bucket residency (``pages * page_size``) on the paged
  layout, the fixed ``capacity`` on the contiguous ring. That asymmetry
  IS the paged layout's perf story, so the gauge must show it.

Peak FLOP/s come from :data:`PEAK_FLOPS_BY_KIND` (per-chip dense
**bf16** marketing peaks, matched on the JAX ``device_kind`` string)
with a ``--peak-flops`` override; CPU devices fall back to
:data:`CPU_NOMINAL_PEAK_FLOPS` so CPU runs still produce a number — an
order-of-magnitude anchor, clearly not a measured roofline (override
it for real CPU studies). An ACCELERATOR kind the table does not know
raises (:class:`UnknownDeviceKind`): a utilization anchored to the
wrong peak is worse than none.

**Precision-aware denominator** (ISSUE 19): the table rows are bf16
peaks, but an fp32 run's matmuls cannot reach them — TPU MXUs run fp32
at half the bf16 rate, so scoring an fp32 run against the bf16 peak
flatters its MFU ~2x. ``peak_flops_per_device(precision=)`` takes the
active precision policy's matmul row (``PrecisionPolicy.mfu_kind`` —
"bf16" or "fp32") and halves the TPU table entry for fp32
(:data:`FP32_PEAK_FRACTION`). The CPU nominal is NOT halved — it is an
fp32-ish anchor already, so every committed CPU artifact is unchanged.
The trainers and the serve scheduler plumb their resolved policy in;
the default keeps the historical bf16 anchoring for direct callers.
"""

from __future__ import annotations

# Per-chip peak dense FLOP/s by device-kind substring (lowercase), most
# specific first. TPU entries are the published bf16 peaks per chip.
# A v5e reports ``device_kind == "TPU v5 lite"``; 197e12 is the Google
# Cloud "TPU v5e" page's bf16 figure.
PEAK_FLOPS_BY_KIND: tuple[tuple[str, float], ...] = (
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# Nominal single-CPU-core fp32 peak (~a few 10s of GFLOP/s with vector
# units): the documented fallback that keeps MFU defined on CPU smoke
# runs. It is an anchor, not a measurement — pass --peak-flops to pin
# a real number.
CPU_NOMINAL_PEAK_FLOPS = 5e10

# TPU MXU fp32 throughput as a fraction of the bf16 peak: fp32 matmuls
# run the same systolic array at half rate on every generation in the
# table above, so an fp32-policy run divides the bf16 row by 2.
FP32_PEAK_FRACTION = 0.5


class UnknownDeviceKind(LookupError):
    """An accelerator whose ``device_kind`` is in neither peak table
    (this module's FLOP/s rows, ``obs.comms``'s interconnect rows).
    Add the row with its source, or pass the override."""


def peak_flops_per_device(device=None, override: float | None = None,
                          precision: str = "bf16") -> float:
    """Peak FLOP/s for one device at the given matmul ``precision``
    ("bf16" or "fp32" — the resolved policy's ``mfu_kind``):
    ``override`` wins (taken as the peak at the ACTIVE precision — the
    operator pinning a roofline pins the one their run can reach); else
    the ``device_kind`` table (bf16 rows, halved for fp32 per
    :data:`FP32_PEAK_FRACTION`); else, for a CPU device (or none), the
    CPU nominal fallback (precision-independent — it is an fp32-ish
    anchor). An ACCELERATOR kind the table doesn't know (a new TPU
    generation, a GPU) raises :class:`UnknownDeviceKind` — anchoring
    its MFU to the CPU nominal would report utilizations orders of
    magnitude above 1.0 as if they were real."""
    if precision not in ("bf16", "fp32"):
        raise ValueError(
            f"unknown peak precision {precision!r} (bf16 or fp32)"
        )
    if override is not None:
        if override <= 0:
            raise ValueError(f"peak flops override must be > 0, got "
                             f"{override}")
        return float(override)
    kind = ""
    if device is not None:
        kind = str(getattr(device, "device_kind", "")).lower()
    for key, peak in PEAK_FLOPS_BY_KIND:
        if key in kind:
            return peak * (FP32_PEAK_FRACTION if precision == "fp32"
                           else 1.0)
    platform = str(getattr(device, "platform", "cpu")).lower()
    if platform != "cpu":
        raise UnknownDeviceKind(
            f"unknown accelerator device_kind {kind!r}: no peak FLOP/s "
            "row in obs.cost.PEAK_FLOPS_BY_KIND — add one with its "
            "source, or pass --peak-flops (peak_flops=) with the chip's "
            "real peak"
        )
    return CPU_NOMINAL_PEAK_FLOPS


def mfu(flops: float, seconds: float, n_devices: int,
        peak_per_device: float) -> float:
    """Model-FLOPs utilization: analytic FLOPs executed over the window
    divided by what ``n_devices`` could have executed at peak."""
    if seconds <= 0 or n_devices < 1 or peak_per_device <= 0:
        return 0.0
    return flops / (seconds * n_devices * peak_per_device)


# -- LM transformer -----------------------------------------------------------


def _lm_block_forward_flops(spec, tokens: int, attend_width: int) -> int:
    """Forward matmul FLOPs of ONE transformer block over ``tokens``
    query rows attending ``attend_width`` key rows: QKV+O projections
    (``8*t*e^2``), attention (``4*t*W*e`` — QK^T + AV), MLP
    (``4*t*e*f``)."""
    e, f = spec.d_model, spec.d_ff
    return (8 * tokens * e * e
            + 4 * tokens * attend_width * e
            + 4 * tokens * e * f)


def lm_forward_flops(spec, batch: int, seq_len: int) -> int:
    """Forward FLOPs of one full-sequence pass: ``num_layers`` blocks
    (full ``T x T`` attention) plus the untied head projection
    (``2*B*T*e*vocab``; the embedding lookup is a gather — no
    matmul)."""
    t = batch * seq_len
    # Per sequence, every one of its T query rows attends its own T key
    # rows: the block helper with tokens=T, width=T, scaled by batch.
    block = batch * _lm_block_forward_flops(spec, seq_len, seq_len)
    return spec.num_layers * block + 2 * t * spec.d_model * spec.vocab


def lm_train_step_flops(spec, batch: int, seq_len: int, *,
                        remat: bool = False) -> int:
    """Forward + backward FLOPs of one LM train step (global batch).
    Backward is 2x forward; ``remat`` adds one extra BLOCK forward per
    layer (the head is not rematerialized). Topology-invariant — see
    the module docstring's mode-awareness note."""
    fwd = lm_forward_flops(spec, batch, seq_len)
    total = 3 * fwd
    if remat:
        total += (spec.num_layers * batch
                  * _lm_block_forward_flops(spec, seq_len, seq_len))
    return total


# -- CNN ----------------------------------------------------------------------

# SAME 5x5 convs at stride 1 keep spatial dims; the 2x2 pool halves them
# (28 -> 14 -> 7 -> 4 -> 2), so each conv stage's output spatial extent
# equals its INPUT extent. The FC input is the 2x2 pooled final stage.
_CNN_SPATIAL = (28, 14, 7, 4)
_CNN_KERNEL = 5 * 5


def cnn_forward_flops(conv_channels=(32, 64, 128, 256),
                      fc_sizes=(1024, 512), num_classes: int = 10,
                      batch: int = 1) -> int:
    """Forward matmul FLOPs of the 4-conv/3-FC MNIST family per
    ``batch`` images: each SAME conv is ``2 * H*W * cout * (25*cin)``
    (identical whether lowered as a conv or a patches-matmul — the
    contraction is the same, which is why ``conv_matmul`` modes need no
    separate accounting), plus the three FC matmuls."""
    cins = (1,) + tuple(conv_channels[:3])
    flops = 0
    for s, cin, cout in zip(_CNN_SPATIAL, cins, conv_channels):
        flops += 2 * s * s * cout * (_CNN_KERNEL * cin)
    f1, f2 = fc_sizes
    flops += 2 * (2 * 2 * conv_channels[3]) * f1
    flops += 2 * f1 * f2
    flops += 2 * f2 * num_classes
    return batch * flops


def cnn_train_step_flops(batch: int, conv_channels=(32, 64, 128, 256),
                         fc_sizes=(1024, 512),
                         num_classes: int = 10) -> int:
    """Forward + backward (2x forward) FLOPs of one CNN train step."""
    return 3 * cnn_forward_flops(conv_channels, fc_sizes, num_classes,
                                 batch)


# -- serving ------------------------------------------------------------------


def serve_decode_flops_per_token(spec, attend_width: int) -> int:
    """Decode FLOPs for ONE token of one slot attending ``attend_width``
    resident rows — the paged-aware width: ``pages * page_size`` of the
    decode bucket on the paged layout, ``capacity`` on the contiguous
    ring (serve/engine.py sets ``last_attend_width`` accordingly)."""
    return (spec.num_layers
            * _lm_block_forward_flops(spec, 1, attend_width)
            + 2 * spec.d_model * spec.vocab)


def serve_speculate_verify_flops(spec, fed_rows: int,
                                 attend_width: int) -> int:
    """One speculative verify call (ISSUE 15): ``fed_rows`` decode-
    shaped rows — the real active slots PLUS every draft lane — each
    attending ``attend_width`` resident rows. The verify is literally
    the decode program with lanes riding in free slots, so its cost is
    per-token decode cost times the rows actually computed; emitted
    tokens can be fewer (rejected lanes) or more (a fully-accepted
    block's bonus token) — the asymmetry IS the speculation trade, so
    the accounting must price rows, not tokens."""
    return fed_rows * serve_decode_flops_per_token(spec, attend_width)


def serve_prefill_flops(spec, tokens: int, attend_width: int) -> int:
    """Prefill FLOPs for a ``tokens``-row block whose attention spans
    ``attend_width`` rows (the compiled bucket width — padding computes
    too; honesty about the bucket is the point)."""
    return (spec.num_layers
            * _lm_block_forward_flops(spec, tokens, attend_width)
            + 2 * tokens * spec.d_model * spec.vocab)
