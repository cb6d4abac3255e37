"""Readers of the two-mixer block's per-layer metrics, named in a
metric's file as ``"reader": "sala_readers.<function>"``.

They read what ``perf/readers.py`` reads (the recorder's device calls
and their programs' device time) and the counters the engine puts on the
scheduler's spans for a pattern with linear or sparse layers:
``serve.decode`` carries ``kv_pages`` (pages that hold the active slots'
rows, a K/V head of one sparse layer), ``sparse_pages`` (those a sparse
layer attends) and ``state_slots`` (slots whose state the tick reads and
writes); ``serve.prefill`` carries ``chunk`` (the block's index in its
prompt: its first position is ``chunk x prefill_chunk``) and ``sparse``
(1 where the program ran the selector). A program that writes none of
them, or runs no kernel named ``sparse_decode_attention``, gives
``None`` from the readers that need it: nothing raises.
"""

from __future__ import annotations

from . import readers, sala_counts, span_readers, trace_reduce

COUNTERS = ("kv_pages", "sparse_pages", "state_slots")


def _decodes(ctx: dict) -> list:
    """The window's decode calls, each with its program's device time
    and its span's counters; nothing where the three do not pair up."""
    calls = readers._device_calls(ctx, "decode")
    spans = span_readers.spans(ctx, span_readers.DECODE)
    if not calls or len(calls) != len(spans) \
            or any(k not in s[3] for s in spans for k in COUNTERS):
        return []
    return [dict(c, **s[3]) for c, s in zip(calls, spans)]


def serve_mfu(ctx: dict, args: dict):
    """Forward FLOPs of the prompt and output tokens of the traced
    window over the window and the bf16 peak; a prefill block's first
    position and form from its span's counters."""
    calls = ctx["facts"]["traced_calls"]
    if not calls or not trace_reduce.device_planes(ctx["trace"]):
        return None
    spans = span_readers.spans(ctx, span_readers.PREFILL)
    blocks = [c for c in calls if c["kind"] == "prefill"]
    if len(spans) != len(blocks) \
            or any("chunk" not in s[3] or "sparse" not in s[3] for s in spans):
        return None
    chunk = ctx["cell"]["engine"].get("prefill_chunk", 0)
    ctx.setdefault("notes", {})["sparse_prefill_blocks"] = [
        sum(s[3]["sparse"] for s in spans), len(spans)]
    flops = sala_counts.serve_flops(
        ctx["sizes"],
        [(s[3]["chunk"] * chunk, c["tokens"], bool(s[3]["sparse"]))
         for s, c in zip(spans, blocks)],
        [n for c in calls if c["kind"] == "decode" for n in c["contexts"]])
    window = trace_reduce.busy(ctx["trace"])["window_s"]
    return 100.0 * flops / window / ctx["peaks"]["bf16_flops"]


def decode_hbm_roofline(ctx: dict, args: dict):
    """Mean over the traced decode ticks of (bytes the tick has to move /
    HBM bandwidth) / the tick's device time."""
    ticks = _decodes(ctx)
    if not ticks:
        return None
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    shares = [sala_counts.decode_tick_bytes(
        ctx["sizes"], t["sparse_pages"], t["state_slots"], t["contexts"])
        / bw / t["device_s"] for t in ticks]
    return 100.0 * sum(shares) / len(shares)


def sparse_attn_roofline(ctx: dict, args: dict):
    """The least time the chip could take to read K and V of the pages
    the traced decode ticks' sparse layers attend, over the summed device
    time of the kernel's events (``args["pattern"]``). Bytes-bound: a
    page's products at the bf16 peak take a fifteenth of its bytes'
    time."""
    ticks = _decodes(ctx)
    got = trace_reduce.op_seconds(ctx["trace"], args["pattern"])
    if not ticks or not got["count"]:
        return None
    moved = sum(sala_counts.attended_page_bytes(ctx["sizes"],
                                                t["sparse_pages"])
                for t in ticks)
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / got["seconds"]


def sparse_pages_read_pct(ctx: dict, args: dict):
    """Pages the window's decode ticks attend over the pages that hold
    their slots' rows."""
    spans = [s[3] for s in span_readers.spans(ctx, span_readers.DECODE)]
    if not spans or any(k not in a for a in spans for k in COUNTERS[:2]) \
            or not sum(a["kv_pages"] for a in spans):
        return None
    return 100.0 * sum(a["sparse_pages"] for a in spans) \
        / sum(a["kv_pages"] for a in spans)
