"""Long-sequence LM row on the 8-device virtual mesh (seq >= 8192).

Hardware-independent evidence that the long-context story holds END TO
END at a length that could not fit one device's score memory: the
PRODUCT ``SeqTrainer`` trains the decoder LM with the sequence sharded
over 8 devices (ring attention), and the row records

- tokens/s through the product span program (virtual-mesh CPU — an
  *algorithmic* number like scaling.py's, not an ICI/MXU one);
- the compiled span program's per-device temp bytes from XLA's memory
  analysis, next to the same program compiled at W=2, pinning the
  O(T^2/W) saved-residual law at the 8192 scale (the test-suite twin,
  tests/test_lm.py::test_seq_trainer_activation_memory_scales_with_shard,
  runs at T=1024 to stay fast);
- both position layouts (contiguous + zigzag), so the balanced layout's
  exactness is demonstrated at depth as well as in the unit tests.

Usage:
    python benchmarks/lm_longseq.py [--seq-len 8192] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddl_tpu.parallel.mesh import virtual_cpu_mesh  # noqa: E402


def measure(seq_len: int, workers: int, layout: str, steps: int,
            batch: int, spec, remat: bool = False) -> dict:
    import jax.numpy as jnp

    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
    from ddl_tpu.train.trainer import force

    ds = synthesize_copy(
        num_train=batch * steps, num_test=batch, seq_len=seq_len,
        vocab=spec.vocab, seed=0,
    )
    cfg = SeqConfig(
        epochs=1, batch_size=batch, eval_every=0, num_workers=workers,
        scheme="ring", seq_layout=layout, remat=remat, spec=spec,
    )
    tr = SeqTrainer(cfg, ds)
    xs = tr.stage_batches(ds.tokens, steps, batch)
    ys = tr.stage_batches(ds.targets, steps, batch)
    ws = tr.stage_batches(ds.weights, steps, batch)
    compiled = tr.span_program(steps).lower(
        tr.params, tr.opt_state, xs, ys, ws, jnp.int32(0)
    ).compile()
    mem = compiled.memory_analysis()
    force((xs, ys, ws))
    t0 = time.perf_counter()
    p, o, loss = compiled(tr.params, tr.opt_state, xs, ys, ws, jnp.int32(0))
    loss = float(loss)  # the fetch closes the bracket
    dt = time.perf_counter() - t0
    assert loss == loss, "non-finite loss"  # NaN guard
    return {
        "seq_len": seq_len,
        "workers": workers,
        "layout": layout,
        "remat": remat,
        "tokens_per_sec": round(steps * batch * seq_len / dt, 1),
        "steps": steps,
        "loss": round(loss, 4),
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    from ddl_tpu.utils import compile_cache

    compile_cache.enable()
    virtual_cpu_mesh(8)  # a CPU-mesh memory audit by definition
    from ddl_tpu.models.transformer import LMSpec

    # Small widths keep the CPU runtime in minutes; the sequence length is
    # the thing being demonstrated, and attention dominates at 8192.
    spec = LMSpec(vocab=32, d_model=64, num_heads=4, num_layers=2, d_ff=128)

    rows = [
        measure(args.seq_len, 8, "contiguous", args.steps, args.batch, spec),
        measure(args.seq_len, 8, "zigzag", args.steps, args.batch, spec),
        # Remat: same loss, ~1/3 extra compute, saved-residual memory
        # /100x (the framework-level number is pinned by
        # tests/test_lm.py::test_seq_trainer_remat_same_numbers_less_memory;
        # this row records the tokens/s COST of the trade end-to-end).
        measure(args.seq_len, 8, "contiguous", args.steps, args.batch,
                spec, remat=True),
        # The W=2 comparison point for the per-device memory law; one
        # step only (the quadratic score tiles make it the slow arm).
        measure(args.seq_len, 2, "contiguous", 1, args.batch, spec),
    ]
    # Select by attributes, not position — inserting a row must not be
    # able to silently re-point the ratio (review finding r5).
    w8 = next(r for r in rows if r["workers"] == 8 and not r["remat"]
              and r["layout"] == "contiguous")
    w2 = next(r for r in rows if r["workers"] == 2)
    out = {
        "platform": "cpu-virtual-mesh",
        "spec": {"d_model": spec.d_model, "heads": spec.num_heads,
                 "layers": spec.num_layers, "d_ff": spec.d_ff,
                 "vocab": spec.vocab},
        "rows": rows,
        "mem_ratio_w2_over_w8": round(
            w2["temp_bytes_per_device"] / w8["temp_bytes_per_device"], 2
        ),
        "note": "virtual-mesh algorithmic row (VERDICT r4 task 5): "
                "tokens/s is a CPU number; the memory law and the "
                "zigzag-vs-contiguous loss agreement are the evidence",
    }
    line = json.dumps(out)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
