"""The tests' rehearsal of the latent-attention routed-expert block's
cell: the cell's own files, runner, comparison and readers at toy sizes
on whatever backend there is, as ``perf/mimo_rehearsal.py`` is for its
family. Nothing measured here is a device number, and nothing is printed.
"""

from __future__ import annotations

import argparse
import copy

from . import harness, k2_weights as kw

# Two latent layers, dense + routed, a rank of 4 of 16 experts, a shared
# expert, YaRN with its ramp (low 0, high 3) inside the 8 frequencies.
TINY = kw.K2Sizes(
    name="tiny", vocab=64, d_model=32, num_heads=4, q_lora=24, kv_lora=16,
    nope_dim=8, rope_dim=16, v_head_dim=8, rope_base=10_000.0,
    rope_factor=4.0, rope_original=64, beta_fast=8.0, beta_slow=1.0,
    mscale=1.0, mscale_all_dim=1.0, d_ff=64, expert_ff=16, shared_ff=16,
    router_width=16, experts_held=(4, 8), top_k=4, route_scale=2.5,
    ffn_kinds=(0, 1), eps=1e-6)
# The two numbers the cell holds, read on the CPU as the cell's own are
# read on the chip. The toy computes in fp32, for mimo_rehearsal's reason:
# at this width bf16 flips an expert now and then, and one flipped token
# reads above the fp8 control's lowest. Over six seeds (PR 32; samples of
# six requests, 100-200 tokens: over three, one seed's control agreed with
# the reference on all of its 30 tokens) the program reads 0.0 in both on
# every seed, the fp8 control 0.00178 and 0.0348 at least. The precision
# itself is read on the chip (`perf/k2_limits.py`).
LIMITS = {"logit_gap_mean": 0.0005, "logit_gap_p99": 0.012,
          "requests_failed": 0, "compiles_in_window": 0}


def shrink(cell: dict):
    """The cell with every length cut to a toy's; its structure stays."""
    cell = copy.deepcopy(cell)
    cell["check"].update(limits=dict(LIMITS), requests=6, pad_to=32)
    t = cell["traffic_params"]
    for key in ("prompt", "output"):
        for field in ("median", "min", "max"):
            t[key][field] = max(2, t[key][field] // 16)
    t["block"], t["clients"] = 8, 4
    e = cell["engine"]
    e["page_size"], e["compute_dtype"] = 4, "float32"
    e["capacity"] = -(-(t["prompt"]["max"] + t["output"]["max"]) // 8) * 8
    e["num_pages"] = t["clients"] * e["capacity"] // 4
    if e.get("prefill_chunk"):
        e["prefill_chunk"] = 16
    cell["trace_seconds"] = 1.0
    return cell, TINY


def run_cell(name: str, seed: int, seconds: float, trace: bool) -> dict:
    """``run.run_cell(..., rehearse=True)`` for this block's cells."""
    from . import serve_k2_runner as runner

    cell, sizes = shrink(harness.load_cell(name))
    devices = harness.find_devices(cell["chips"], True)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              rehearse=True)
    out = runner.run(cell, sizes, args, devices, harness.now(),
                     harness.CompileCounter())
    return {"correct": harness.judge(out["checked"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": harness.metric_values(cell, trace, out["end_to_end"],
                                             out["per_layer"]),
            "checked": out["checked"], "info": out["info"],
            "memory_peak_bytes": out["memory_peak_bytes"]}
