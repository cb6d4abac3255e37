"""The tests' rehearsal: the same files and control flow at toy sizes.

Only ``perf/tests`` use it, through ``run.run_cell(..., rehearse=True)``.
Nothing measured in a rehearsal is a device number, and the command
prints none of it.
"""

from __future__ import annotations

import copy

from . import weights

TINY = weights.Sizes(name="tiny", vocab=32, d_model=32, num_heads=2,
                     num_layers=2, d_ff=64)
# The limits at the toy's size, read on the CPU (PR 25) as the cells' own
# are read on the chip. Training, three seeds: the program reads losses
# within 1.0e-3, gradient difference 0.023, gradient gap 0.0079, change gap
# 0.0070 at most; the fp8 control's gradient difference 0.128 and gradient
# gap 0.028 at least; half a batch left out 0.66, 0.29 and 0.065. Serving:
# the program's widest gap 0.009 (mean 0.0003) at most, the control's widest
# 0.087 (mean 0.0032) at least.
LIMITS = {
    "train": {"loss_step1_rel": 5e-3, "loss_step2_rel": 5e-3,
              "loss_step3_rel": 5e-3, "gradient_diff_rel": 0.07,
              "gradient_norm_gap": 0.02,
              "change_norm_gap": 0.03, "compiles_in_window": 0},
    "serve": {"logit_gap_p99": 0.04, "logit_gap_mean": 0.001,
              "requests_failed": 0, "compiles_in_window": 0},
}


def shrink(cell: dict):
    """The cell with every length cut to a toy's; its structure stays."""
    cell = copy.deepcopy(cell)
    cell["check"]["limits"] = dict(LIMITS[cell["runner"]])
    t = cell["traffic_params"]
    if cell["runner"] == "train":
        t["seq_len"] = 128 * cell["trainer"].get("num_workers", 1)
        t["staged_batches"] = 4
        cell["trace_seconds"] = 1.0
    else:
        for key in ("prompt", "output"):
            t[key]["median"] = max(2, t[key]["median"] // 16)
            t[key]["min"] = max(2, t[key]["min"] // 16)
            t[key]["max"] = max(4, t[key]["max"] // 16)
        t["block"] = 16
        e = cell["engine"]
        e["page_size"] = max(2, e["page_size"] // 8)
        e["capacity"] = t["prompt"]["max"] + t["output"]["max"]
        e["capacity"] = -(-e["capacity"] // e["page_size"]) * e["page_size"]
        cell["trace_seconds"] = 1.0
        cell["check"]["requests"] = 3
        cell["check"]["pad_to"] = 32
    return cell, TINY
