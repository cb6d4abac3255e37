"""The per-layer metrics' readers, found by the name a metric's file gives.

A reader gets the run's context (the reduced trace, the runner's facts,
the cell, the sizes, the chip's peaks) and the ``args`` of its metric's
file, and returns a number, or ``None`` where it found nothing to read:
the metric is then left out of the result line. No reader returns 0 for
a share of a roofline or of a peak.
"""

from __future__ import annotations

import importlib

from . import counts, harness, trace_reduce


def train_mfu(ctx: dict, args: dict):
    """Model FLOPs per token x tokens per second of the traced window
    over chips x peak; remat's recompute is not in the count."""
    if not trace_reduce.device_planes(ctx["trace"]):
        return None
    t = ctx["cell"]["traffic_params"]
    flops = counts.train_flops_per_token(ctx["sizes"], t["seq_len"])
    peak = ctx["peaks"]["bf16_flops"] * len(ctx["devices"])
    return 100.0 * flops * ctx["facts"]["tokens_per_s"] / peak


def kernel_roofline(ctx: dict, args: dict):
    """Sum over the kernel's calls of max(FLOPs / peak, bytes / HBM
    bandwidth) over the summed device time of its events. Each entry of
    ``kernels`` names the events (``pattern``) and what one call is made
    of (``products`` T x T x D matrix products a head, ``arrays``
    [B, H, T, D] arrays read or written once)."""
    t, tr = ctx["cell"]["traffic_params"], ctx["cell"]["trainer"]
    sizes, peaks = ctx["sizes"], ctx["peaks"]
    rows = t["batch"] // tr.get("data_parallel", 1)
    shape = (rows, sizes.num_heads, t["seq_len"], sizes.head_dim)
    least = spent = 0.0
    bound = {"flops": 0, "bytes": 0}
    for k in args["kernels"]:
        got = trace_reduce.op_seconds(ctx["trace"], k["pattern"])
        if not got["count"]:
            continue
        by_flops = counts.flash_call_flops(*shape, k["products"]) \
            / peaks["bf16_flops"]
        by_bytes = counts.flash_call_bytes(*shape, k["arrays"]) \
            / peaks["hbm_bytes_per_s"]
        bound["flops" if by_flops >= by_bytes else "bytes"] += got["count"]
        least += got["count"] * max(by_flops, by_bytes)
        spent += got["seconds"]
    if not spent:
        return None
    ctx.setdefault("notes", {})[args.get("note", "kernel_bound")] = bound
    return 100.0 * least / spent


def device_idle(ctx: dict, args: dict):
    """1 - (union of op intervals / traced window), the chip with most."""
    b = trace_reduce.busy(ctx["trace"])
    if not b["busy_s_per_chip"] or not b["window_s"]:
        return None
    return 100.0 * (1.0 - min(b["busy_s_per_chip"]) / b["window_s"])


def collective_exposed(ctx: dict, args: dict):
    """Time inside collectives with no other op running on that chip,
    over the traced window, the worst chip."""
    got = trace_reduce.exposed_collectives(ctx["trace"])
    if not any(c["collective_s"] for c in got["per_chip"]):
        return None
    return 100.0 * max(c["exposed_s"] for c in got["per_chip"]) \
        / got["window_s"]


def _device_calls(ctx: dict, kind: str):
    """The serve facts' device calls of one kind (``prefill`` or
    ``decode``), each with the device time of its program. The engine's
    programs are all named ``jit_run`` and run one at a time, each
    fetched before the next starts, so the k-th program of the trace is
    the k-th call the recorder saw; where the counts differ nothing is
    read."""
    calls = ctx["facts"]["traced_calls"]
    mods = trace_reduce.modules(ctx["trace"], ctx["cell"]["program_pattern"])
    if not calls or len(calls) != len(mods):
        ctx.setdefault("notes", {})["unmatched_programs"] = [len(calls),
                                                             len(mods)]
        return []
    return [dict(c, device_s=m[1] / 1e9)
            for c, m in zip(calls, mods) if c["kind"] == kind]


def decode_step_ms(ctx: dict, args: dict):
    calls = _device_calls(ctx, "decode")
    if not calls:
        return None
    return 1e3 * harness.median([c["device_s"] for c in calls])


def decode_hbm_roofline(ctx: dict, args: dict):
    """Mean over the traced decode ticks of (bytes the tick has to read
    / HBM bandwidth) / the tick's device time. Bandwidth-bound: the
    tick's FLOPs at peak take a fifteenth of its bytes at peak."""
    calls = _device_calls(ctx, "decode")
    if not calls:
        return None
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    shares = [counts.decode_tick_bytes(ctx["sizes"], c["resident_tokens"])
              / bw / c["device_s"] for c in calls]
    return 100.0 * sum(shares) / len(shares)


def prefill_tokens_per_s(ctx: dict, args: dict):
    calls = _device_calls(ctx, "prefill")
    if not calls:
        return None
    return sum(c["tokens"] for c in calls) / sum(c["device_s"] for c in calls)


def serve_mfu(ctx: dict, args: dict):
    """Forward FLOPs of the prompt and output tokens processed in the
    traced window over the window and the peak."""
    calls = ctx["facts"]["traced_calls"]
    if not calls or not trace_reduce.device_planes(ctx["trace"]):
        return None
    flops = counts.serve_flops(
        ctx["sizes"],
        [c["tokens"] for c in calls if c["kind"] == "prefill"],
        [n for c in calls if c["kind"] == "decode" for n in c["contexts"]])
    window = trace_reduce.busy(ctx["trace"])["window_s"]
    return 100.0 * flops / window / ctx["peaks"]["bf16_flops"]


READERS = {f.__name__: f for f in (
    train_mfu, kernel_roofline, device_idle, collective_exposed,
    decode_step_ms, decode_hbm_roofline, prefill_tokens_per_s, serve_mfu)}


def find_reader(name: str):
    """A function of this module by its name, or ``<module>.<function>``
    of another module under ``perf/``: a later PR's reader is a file of
    its own."""
    if "." in name:
        module, name = name.rsplit(".", 1)
        return getattr(importlib.import_module(f"{__package__}.{module}"), name)
    return READERS[name]


def read_all(ctx: dict):
    """Every per-layer metric of the cell: ``(values, device fields,
    breakdown)``."""
    ctx["trace"] = trace_reduce.load(ctx["trace_path"])
    # A rehearsal (tests only, never printed) borrows the v5e's row.
    ctx["peaks"] = counts.peaks("TPU v5 lite" if ctx.get("rehearse")
                                else ctx["devices"][0].device_kind)
    values = {}
    for m in ctx["cell"]["per_layer"]:
        values[m["name"]] = find_reader(m["reader"])(ctx, m.get("args", {}))
    b = trace_reduce.busy(ctx["trace"])
    device = {}
    if b["busy_s_per_chip"]:
        device = {"busy_s": sum(b["busy_s_per_chip"])
                  / len(b["busy_s_per_chip"]), "window_s": b["window_s"]}
    return values, device, trace_reduce.breakdown(
        ctx["trace"], ctx["cell"].get("host_spans"))
