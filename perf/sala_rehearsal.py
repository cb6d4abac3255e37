"""The tests' rehearsal of the two-mixer block's cell: the cell's own
files, runner, comparison and readers at toy sizes on whatever backend
there is, as ``perf/k2_rehearsal.py`` is for its block. Nothing measured
here is a device number, and nothing is printed.
"""

from __future__ import annotations

import argparse
import copy

from . import harness, sala_weights as sw

# One period twice over, as the cell's: sparse, two linear, sparse. The
# selector's sizes shrunk with the lengths so that selection binds: pages
# of 8 rows, 8 of a slot's up to 140 attended past 128 rows of context.
TINY = sw.SalaSizes(
    name="tiny", vocab=512, d_model=32, num_heads=4, head_dim=8, kv_heads=2,
    d_ff=64, mixers=(sw.SPARSE, sw.LINEAR, sw.LINEAR, sw.SPARSE), eps=1e-6,
    rope_base=10_000.0, scale_emb=12.0, scale_depth=1.4, depth=32,
    dim_model_base=16, kernel_size=4, kernel_stride=2, block_size=8, topk=8,
    init_blocks=1, window_size=32, dense_len=128)
# The two numbers the cell holds, read on the CPU as the cell's own are
# read on the chip. The toy computes in fp32: the precision itself is
# read on the chip (``perf/sala_limits.py``). Over six seeds (PR 34;
# samples of six requests, 122-230 tokens) every served token IS the
# reference's first choice, so the program reads 0.0 in both; the fp8
# control's first choices read 3.5e-5 to 6.8e-4 in the mean and 0.0073 to
# 0.0166 at the 99th percentile, on one seed 0.0 there (one flipped token
# of 122, which the mean alone holds). The vocabulary is 512, not 64: at
# 64 a seed's control agreed with the reference on every token.
LIMITS = {"logit_gap_mean": 1e-5, "logit_gap_p99": 0.003,
          "requests_failed": 0, "compiles_in_window": 0}


def shrink(cell: dict):
    """The cell with every length cut to a toy's; its structure stays."""
    cell = copy.deepcopy(cell)
    cell["check"].update(limits=dict(LIMITS), requests=6, pad_to=32)
    t = cell["traffic_params"]
    for key in ("prompt", "output"):
        for field in ("median", "min", "max"):
            t[key][field] = max(2, t[key][field] // 32)
    t["block"], t["clients"] = 8, 4
    e = cell["engine"]
    e["page_size"], e["compute_dtype"] = TINY.block_size, "float32"
    e["capacity"] = -(-(t["prompt"]["max"] + t["output"]["max"]) // 32) * 32
    e["num_pages"] = t["clients"] * e["capacity"] // e["page_size"]
    if e.get("prefill_chunk"):
        e["prefill_chunk"] = 64
    cell["trace_seconds"] = 1.0
    return cell, TINY


def run_cell(name: str, seed: int, seconds: float, trace: bool) -> dict:
    """``run.run_cell(..., rehearse=True)`` for this block's cells."""
    from . import serve_sala_runner as runner

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
