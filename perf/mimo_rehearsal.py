"""The tests' rehearsal of the window/global routed-expert family's cell:
the cell's own files, runner, comparison and readers at toy sizes on
whatever backend there is. ``perf/rehearsal.py`` shrinks the dense
family's cells and knows no other runner, so this family brings its own.
Nothing measured here is a device number, and nothing is printed.
"""

from __future__ import annotations

import argparse
import copy

from . import harness, mimo_weights as mw

TINY = mw.MimoSizes(
    name="tiny", vocab=64, d_model=32, num_heads=4, head_dim=12,
    v_head_dim=8, kv_heads=(1, 2), rope_base=(5_000_000.0, 10_000.0),
    rotary_dim=4, window=8, value_scale=0.707, d_ff=64, expert_ff=16,
    router_width=16, experts_held=(0, 16), top_k=4,
    layer_kinds=(0, 1, 1, 0), ffn_kinds=(0, 1, 1, 1), eps=1e-5)
# The two numbers the cell holds, read on the CPU over five seeds (PR 28) as
# the cell's own are read on the chip. The toy computes in fp32: at its
# width bf16 flips an expert on one token in fifty, and that one token reads
# 0.011 in the mean and 0.27 in the tail, above the control's lowest (0.0068
# and 0.087), so no limit on these two separates a bf16 toy. In fp32 the
# program reads 0.0 on every seed in both, the fp8 control 0.0105 and 0.134
# at least. The precision itself is read on the chip (`perf/mimo_limits.py`).
LIMITS = {"logit_gap_mean": 0.003, "logit_gap_p99": 0.04,
          "requests_failed": 0, "compiles_in_window": 0}


def shrink(cell: dict):
    """The cell with every length cut to a toy's; its structure stays."""
    cell = copy.deepcopy(cell)
    cell["check"].update(limits=dict(LIMITS), requests=3, pad_to=32)
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
    """``run.run_cell(..., rehearse=True)`` for this family's cells."""
    from . import serve_mimo_runner as runner

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
