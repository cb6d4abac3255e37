"""Pipeline bubble: measured step time vs the analytic schedule model.

The schedule model (``ddl_tpu.pipeline.schedule``) says a pipeline step
runs ``2 * (M + pp - 1)`` equal-cost ticks for ``M`` microbatches over
``pp`` stages — ``2M`` of them doing useful work per stage — so the
bubble fraction is ``(pp - 1) / (M + pp - 1)`` for BOTH schedules
(GPipe and 1F1B differ in in-flight MEMORY, not tick count), and step
time at fixed per-microbatch work should scale as ``(M + pp - 1) / M``.

This sweep falsifies that against wall-clock: for each schedule and
``M ∈ {1, 2, 4, 8}`` (microbatch SIZE held constant, so per-tick work
is constant and total useful work scales with M) it times the compiled
pipeline step (``pipeline.make_pipeline_program`` — the same program
``SeqTrainer`` spans; the M=1 zero-pipelining anchor is constructible
only here, the trainer's topology validation rejects it), fits the
per-tick cost from the largest-M row, and reports::

    measured_bubble(M) = 1 - (2*M * t_tick) / t_step(M)
    predicted_bubble(M) = (pp - 1) / (M + pp - 1)

Usage:
    python benchmarks/pipeline_bubble.py [--pp 2] [--reps 3]
        [--json out.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--microbatches", type=int, nargs="+",
                    default=[1, 2, 4, 8])
    ap.add_argument("--microbatch-size", type=int, default=4,
                    help="sequences per microbatch (held constant across "
                         "the sweep so per-tick work is constant)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--schedules", nargs="+", default=["gpipe", "1f1b"])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--json", dest="json_path", default=None)
    args = ap.parse_args()

    from ddl_tpu.parallel.mesh import virtual_cpu_mesh
    from ddl_tpu.utils import compile_cache

    compile_cache.enable()
    virtual_cpu_mesh(args.pp)

    import jax

    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.models.transformer import TINY_SPEC
    from ddl_tpu.pipeline import make_pipeline_program, predicted_bubble
    from ddl_tpu.pipeline.schedule import max_in_flight, schedule_tables
    from ddl_tpu.strategies.seq import SeqConfig

    pp = args.pp
    mbs = args.microbatch_size
    rows = []
    for kind in args.schedules:
        for m in args.microbatches:
            batch = mbs * m
            ds = synthesize_copy(num_train=batch, num_test=2,
                                 seq_len=args.seq_len,
                                 vocab=TINY_SPEC.vocab, seed=0)
            cfg = SeqConfig(
                num_workers=1, scheme="full", batch_size=batch,
                pipeline_parallel=pp, microbatches=m,
                pipeline_schedule=kind, spec=TINY_SPEC,
            )
            fn, state = make_pipeline_program(
                cfg, ds.tokens[:batch], ds.targets[:batch],
                ds.weights[:batch],
            )
            params, opt, xs, ys, ws = state
            # Warmup compiles; every timed bracket closes with the host
            # fetch of the loss.
            _, _, l = fn(params, opt, xs, ys, ws)
            float(l)
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                _, _, l = fn(params, opt, xs, ys, ws)
                float(l)
                times.append(time.perf_counter() - t0)
            f_tab, b_tab = schedule_tables(kind, pp, m)
            rows.append({
                "schedule": kind,
                "microbatches": m,
                "ticks": int(f_tab.shape[1]),
                "in_flight": max_in_flight(f_tab, b_tab),
                "step_s_best": min(times),
                "step_s_median": sorted(times)[len(times) // 2],
                "predicted_bubble": predicted_bubble(pp, m),
            })
            print(f"[pipeline_bubble] {kind} M={m}: "
                  f"{min(times) * 1e3:.1f}ms best "
                  f"({f_tab.shape[1]} ticks, "
                  f"{rows[-1]['in_flight']} in-flight)", file=sys.stderr)

    # Per-tick cost fitted from the largest-M row of each schedule (most
    # work per bubble tick -> best-conditioned fit). Measured bubble =
    # idle-time fraction under the equal-cost-tick model — reported for
    # every row EXCEPT the fit row, whose measured value equals the
    # prediction by algebra (t_tick = step/ticks makes
    # 1 - 2M*t_tick/step ≡ (pp-1)/(M+pp-1)), so quoting it as a match
    # would be circular; it is flagged fit_row instead.
    for kind in args.schedules:
        krows = [r for r in rows if r["schedule"] == kind]
        ref = max(krows, key=lambda r: r["microbatches"])
        t_tick = ref["step_s_best"] / ref["ticks"]
        ref["fit_row"] = True
        for r in krows:
            if r is ref:
                print(f"[pipeline_bubble] {kind} M={r['microbatches']}: "
                      f"t_tick fit row ({t_tick * 1e3:.2f}ms/tick) — "
                      "excluded from measured-vs-predicted",
                      file=sys.stderr)
                continue
            ideal = 2 * r["microbatches"] * t_tick
            r["measured_bubble"] = round(
                max(0.0, 1.0 - ideal / r["step_s_best"]), 4
            )
            print(f"[pipeline_bubble] {kind} M={r['microbatches']}: "
                  f"measured bubble {r['measured_bubble']:.3f} vs "
                  f"predicted {r['predicted_bubble']:.3f}",
                  file=sys.stderr)

    platform = jax.devices()[0].platform
    out = {
        "metric": "lm_pipeline_bubble_fraction",
        "platform": platform,
        "pp": pp,
        "microbatch_size": mbs,
        "seq_len": args.seq_len,
        "spec": dataclasses.asdict(TINY_SPEC),
        "rows": rows,
    }
    line = json.dumps(out)
    print(line)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
