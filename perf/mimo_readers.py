"""Readers of the window/global routed-expert family's per-layer
metrics, named in a metric's file as ``"reader":
"mimo_readers.<function>"``.

They read what ``perf/readers.py`` reads (the recorder's device calls
and their programs' device time) and the counters the family's engine
puts on the scheduler's spans: ``serve.decode`` carries ``moe_assigned``
(assignments of the tick's tokens to held experts, summed over the
routed layers), ``moe_touched`` (held experts with at least one) and
``win_pages`` (window-group pages in use after the tick);
``serve.prefill`` carries ``moe_assigned``. A program that writes none
of them gives ``None`` from every reader here: nothing raises.
"""

from __future__ import annotations

from . import harness, mimo_counts, readers, span_readers, trace_reduce


def _decodes(ctx: dict) -> list:
    """The window's decode calls, each with its program's device time
    and its span's counters; nothing where the three do not pair up."""
    calls = readers._device_calls(ctx, "decode")
    spans = span_readers.spans(ctx, span_readers.DECODE)
    if not calls or len(calls) != len(spans) \
            or any("moe_touched" not in s[3] for s in spans):
        return []
    return [dict(c, **s[3]) for c, s in zip(calls, spans)]


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
    flops = mimo_counts.serve_flops(
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
    shares = [mimo_counts.decode_tick_bytes(
        ctx["sizes"], t["contexts"], t["moe_touched"]) / bw / t["device_s"]
        for t in ticks]
    return 100.0 * sum(shares) / len(shares)


def moe_tokens_per_expert(ctx: dict, args: dict):
    """Median over decode ticks of assignments to held experts over held
    experts touched, all routed layers together."""
    got = [s[3] for s in span_readers.spans(ctx, span_readers.DECODE)
           if s[3].get("moe_touched")]
    if not got:
        return None
    return harness.median([a["moe_assigned"] / a["moe_touched"] for a in got])


def window_rows_per_slot(ctx: dict, args: dict):
    """Median over decode ticks of the window group's rows in use (pages
    x page size) over the slots that decoded."""
    ticks = _decodes(ctx)
    if not ticks or any("win_pages" not in t for t in ticks):
        return None
    rows = ctx["cell"]["engine"]["page_size"]
    return harness.median([t["win_pages"] * rows / len(t["contexts"])
                           for t in ticks])
