"""Long-context LM training throughput on the real chip.

Imports JAX, requires a TPU, runs, fails loudly: a row whose every
attention arm raised is an error, not a row.

Times the PRODUCT sequence-parallel span program (``SeqTrainer.span_program``
— the same compiled object ``python -m ddl_tpu lm`` dispatches) at a
sweep of sequence lengths on a 1-chip mesh, bf16, with bench.py's
methodology: AOT compile outside the bracket, repeats of whole-span
dispatches, every bracket closed by ``trainer.force``
(``jax.block_until_ready`` — BASELINE.md "measurement integrity").

Reports tokens/s and an analytic MFU: train FLOPs/token =
``6*P_mat + 6*L*T_eff*d`` with ``T_eff = T/2`` (causal), where ``P_mat``
counts matmul parameters (blocks + output head; the embedding gather is
not a matmul). One chip has no sequence to shard (scheme=full), so the
sweep compares the LOCAL kernels head-to-head per sequence length:
the xla einsum softmax vs the Pallas flash-attention kernel
(``--attn-impls``). The cross-chip schemes' *program structure* is
covered by the virtual-mesh scaling proxy and tests/test_ring.py, and
their memory law (O(T/P * T/P) scores/device) by
test_ring_attention_memory_is_blockwise.

    python benchmarks/lm_bench.py --json benchmarks/results/lm_tpu.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def flops_per_token(spec, seq_len: int) -> float:
    """Train FLOPs/token, PaLM-style accounting: 6 (fwd+bwd) per matmul
    param, plus attention's two score matmuls (QK^T and AV — each
    2*T_eff*e fwd per token, x3 for fwd+bwd) at causal T_eff = T/2."""
    e, f, L = spec.d_model, spec.d_ff, spec.num_layers
    p_mat = L * (4 * e * e + 2 * e * f) + e * spec.vocab
    return 6.0 * p_mat + 12.0 * L * (seq_len / 2.0) * e


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-lens", type=int, nargs="+",
                    default=[512, 1024, 2048, 4096])
    ap.add_argument("--tokens-per-batch", type=int, default=8192,
                    help="global batch in tokens; sequences/batch = this // T")
    ap.add_argument("--span", type=int, default=8,
                    help="train steps per dispatched span program")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--attn-impls", nargs="+", default=["xla", "flash"],
                    help="local attention kernels to sweep (scheme=full): "
                         "xla einsum softmax vs the Pallas flash kernel")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    from ddl_tpu.utils import compile_cache

    compile_cache.enable()

    import jax.numpy as jnp

    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.models.transformer import LMSpec
    from ddl_tpu.obs import MetricRegistry, cost
    from ddl_tpu.parallel.mesh import device_record, require_tpu
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
    from ddl_tpu.train.trainer import force

    dev = require_tpu()
    spec = LMSpec(vocab=args.vocab, d_model=args.d_model,
                  num_heads=args.heads, num_layers=args.layers,
                  d_ff=args.d_ff)
    peak = cost.peak_flops_per_device(dev)  # raises on an unknown chip
    rows = {}
    # Rep timings go through the obs registry (one labelled histogram
    # series per (T, impl)) and the row stats are read back from it —
    # the bench consumes the product telemetry surface, keeping its
    # percentile math identical to every other consumer's (ISSUE 5).
    reg = MetricRegistry()
    spans = reg.histogram("lm_bench_span_seconds",
                          "wall seconds per timed span dispatch")
    for T in args.seq_lens:
        B = max(1, args.tokens_per_batch // T)
        k = args.span
        ds = synthesize_copy(num_train=B * k, num_test=B, seq_len=T,
                             vocab=args.vocab, seed=0)
        row = {"seqs_per_batch": B}
        for impl in args.attn_impls:
            # One impl crashing (an OOM at the longest sequence, say)
            # must not discard the other arm of the row: record the
            # error field-local and keep going. A row with NO arm left
            # is raised below.
            try:
                cfg = SeqConfig(num_workers=1, scheme="full",
                                compute_dtype="bfloat16", batch_size=B,
                                attn_impl=impl, spec=spec)
                tr = SeqTrainer(cfg, ds)
                xs = tr.stage_batches(ds.tokens, k, B)
                ys = tr.stage_batches(ds.targets, k, B)
                ws = tr.stage_batches(ds.weights, k, B)
                params, opt = tr.params, tr.opt_state
                force((xs, ys, ws, params, opt))
                t0 = time.perf_counter()
                fn = (tr.span_program(k)
                      .lower(params, opt, xs, ys, ws, jnp.int32(0))
                      .compile())
                compile_s = time.perf_counter() - t0
                params, opt, loss = fn(params, opt, xs, ys, ws,
                                       jnp.int32(0))
                force((params, opt, loss))  # warmup
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    params, opt, loss = fn(params, opt, xs, ys, ws,
                                           jnp.int32(0))
                    force((params, opt, loss))
                    spans.observe(time.perf_counter() - t0,
                                  seq_len=T, impl=impl)
            except Exception as e:  # noqa: BLE001 — record, don't discard
                # Structured exception type alongside the message, so a
                # failed arm stays attributable post hoc (a Pallas
                # lowering error or an OOM?) without parsing a truncated
                # prefix out of the string.
                row[impl] = {"error_type": type(e).__name__,
                             "error": f"{type(e).__name__}: {e}"[:300]}
                print(f"[lm_bench] T={T} {impl} FAILED: {e}",
                      file=sys.stderr)
                continue
            times = spans.values(seq_len=T, impl=impl)
            tokens = k * B * T
            best = float(tokens / min(times))
            med = float(np.median([tokens / t for t in times]))
            mfu = round(100.0 * best * flops_per_token(spec, T) / peak, 2)
            row[impl] = {
                "best_tokens_per_s": round(best, 1),
                "median_tokens_per_s": round(med, 1), "mfu_pct": mfu,
                "compile_s": round(compile_s, 1),
            }
            print(f"[lm_bench] T={T} B={B} {impl}: best {best:,.0f} tok/s "
                  f"(median {med:,.0f}, mfu {mfu}%)", file=sys.stderr)
        impls = {k: v for k, v in row.items() if k != "seqs_per_batch"}
        if impls and all("error" in v for v in impls.values()):
            # Every arm raised: a crash, not a measurement.
            raise RuntimeError(f"lm_bench: every arm failed at T={T}: "
                               f"{impls}")
        rows[T] = row  # a failed arm rides along field-local

    out = {
        "metric": "lm_train_tokens_per_sec",
        "device": device_record(),
        "spec": {"d_model": spec.d_model, "heads": spec.num_heads,
                 "layers": spec.num_layers, "d_ff": spec.d_ff,
                 "vocab": spec.vocab,
                 "params": spec.num_params()},
        "span_steps": args.span,
        "results": rows,
    }
    line = json.dumps(out)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
