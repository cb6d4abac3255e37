"""Readers of the latent-attention routed-expert block's per-layer
metrics, named in a metric's file as ``"reader":
"k2_readers.<function>"`` (``moe_tokens_per_expert.k2`` reads through
``mimo_readers.moe_tokens_per_expert``).

They read what ``perf/mimo_readers.py`` reads and one counter more:
``serve.decode`` carries ``latent_rows``, the cached rows the tick's
active slots attend, summed over slots (a host count); and the device
events of the kernel ``latent_decode_attention``. A program that writes
no such counter or runs no such kernel gives ``None`` from the readers
that need it: nothing raises.
"""

from __future__ import annotations

from . import harness, k2_counts, mimo_readers, span_readers, trace_reduce


def _decodes(ctx: dict) -> list:
    """``mimo_readers._decodes`` where every tick has ``latent_rows``."""
    ticks = mimo_readers._decodes(ctx)
    return ticks if all("latent_rows" in t for t in ticks) else []


def serve_mfu(ctx: dict, args: dict):
    """Forward FLOPs of the prompt and output tokens of the traced
    window, the routed experts' by the counted assignments, over the
    window and the bf16 peak."""
    calls = ctx["facts"]["traced_calls"]
    if not calls or not trace_reduce.device_planes(ctx["trace"]):
        return None
    spans = span_readers.spans(ctx, span_readers.DECODE) \
        + span_readers.spans(ctx, span_readers.PREFILL)
    counted = [s[3]["moe_assigned"] for s in spans if "moe_assigned" in s[3]]
    flops = k2_counts.serve_flops(
        ctx["sizes"],
        [c["tokens"] for c in calls if c["kind"] == "prefill"],
        [n for c in calls if c["kind"] == "decode" for n in c["contexts"]],
        sum(counted) if len(counted) == len(calls) else None)
    window = trace_reduce.busy(ctx["trace"])["window_s"]
    return 100.0 * flops / window / ctx["peaks"]["bf16_flops"]


def decode_hbm_roofline(ctx: dict, args: dict):
    """Mean over the traced decode ticks of (bytes the tick has to read /
    HBM bandwidth) / the tick's device time."""
    ticks = _decodes(ctx)
    if not ticks:
        return None
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    shares = [k2_counts.decode_tick_bytes(
        ctx["sizes"], t["latent_rows"], t["moe_touched"]) / bw / t["device_s"]
        for t in ticks]
    return 100.0 * sum(shares) / len(shares)


def latent_attn_roofline(ctx: dict, args: dict):
    """The least time the chip could take for the traced decode ticks'
    latent attention (each cached row attended, read once in every layer
    at HBM bandwidth, or its products at the bf16 peak: the larger) over
    the summed device time of the kernel's events (``args["pattern"]``)."""
    ticks = _decodes(ctx)
    got = trace_reduce.op_seconds(ctx["trace"], args["pattern"])
    if not ticks or not got["count"]:
        return None
    s, peaks = ctx["sizes"], ctx["peaks"]
    by_bytes = k2_counts.latent_row_bytes(s) / peaks["hbm_bytes_per_s"]
    by_flops = s.num_layers * k2_counts.decode_pair_flops(s) \
        / peaks["bf16_flops"]
    ctx.setdefault("notes", {})["latent_attn_bound"] = (
        "bytes" if by_bytes >= by_flops else "flops")
    rows = sum(t["latent_rows"] for t in ticks)
    return 100.0 * rows * max(by_bytes, by_flops) / got["seconds"]


def latent_rows_per_slot(ctx: dict, args: dict):
    """Median over decode ticks of the cached rows attended over the
    slots that decoded: the context the latent pool serves a slot."""
    spans = span_readers.spans(ctx, span_readers.DECODE)
    calls = [c for c in ctx["facts"]["traced_calls"] if c["kind"] == "decode"]
    if not spans or len(spans) != len(calls) \
            or any("latent_rows" not in s[3] for s in spans):
        return None
    return harness.median([s[3]["latent_rows"] / len(c["contexts"])
                           for s, c in zip(spans, calls)])
