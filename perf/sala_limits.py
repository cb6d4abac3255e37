"""Readings for the limits of ``correct`` in the two-mixer block's cells:
``perf/k2_limits.py`` for this runner, not part of a benchmark run.

    python3 perf/sala_limits.py --workload <name> --seeds 6 --control-seeds 3

One process, on the chip, at the cell's own sizes: the engine is built
and warmed up once, then for each seed handed that seed's weights and
driven for a short window at the cell's own load; once it is freed, the
plain reference reads each seed's sample (the program's numbers, the
lower readings) and, on a few seeds, its own fp8 form's first choices
(the control, the upper readings). Each set of numbers then goes through
the comparison that decides ``correct`` (``compare.checked_from`` and
``harness.judge``) with the cell's own limits: the program has to come
out correct on every seed and the control not, by its gaps alone, or the
exit code is 1. The last line of standard output is one JSON object.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3400000003)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from perf import (compare, harness, sala_weights as mw,
                      serve_sala_runner as sr)

    cell = harness.load_cell(args.workload)
    if args.rehearse:
        from perf import sala_rehearsal

        cell, sizes = sala_rehearsal.shrink(cell)
    else:
        from ddl_tpu.utils import compile_cache

        sizes = mw.load_sizes(cell["config"])
        compile_cache.enable()
    devices = harness.find_devices(cell["chips"], args.rehearse)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    engine, scheduler, recorder, Request = sr.build(cell, sizes, seeds[0])
    served, rates = {}, {}
    for i, seed in enumerate(seeds):
        if i:
            engine.params = None  # free the last seed's before the next
            engine.params = mw.make_weights(
                seed, sizes, cell["engine"]["compute_dtype"])
            engine.reset()
            recorder = sr.Recorder()
            scheduler = type(scheduler)(engine, eos_id=None, tracer=recorder)
        run = sr.drive(cell, sizes, seed, scheduler, recorder, Request,
                       args.seconds)
        e2e = sr.end_to_end(run, recorder)
        served[seed] = sr.sample_finished(cell, run, seed)
        rates[seed] = {k: e2e[k] for k in ("serve_tokens_per_s", "ttft_p50_ms",
                                           "itl_p95_ms", "itl_p50_ms",
                                           "failed", "attempted")}
        print(f"seed {seed}: {len(run['done'])} finished, sample of "
              f"{len(served[seed])}, {rates[seed]}", file=sys.stderr,
              flush=True)
    peak = harness.memory_peak_bytes(devices)
    del engine, scheduler, run
    gc.collect()
    out = {"program": {}, "control_fp8": {}}
    limits = cell["check"]["limits"]

    def judged(gaps: dict, failed: int) -> dict:
        """The gaps as the cell's comparison takes them, nothing compiled
        in the window: ``correct`` and the limits passed."""
        checked = compare.checked_from(
            dict(gaps, requests_failed=failed, compiles_in_window=0), limits)
        return dict(gaps, correct=harness.judge(checked),
                    over=[k for k, e in checked.items()
                          if not harness.judge({k: e})])

    for seed in seeds:
        got = sr.reference_gaps(cell, sizes, seed, served[seed],
                                control=seed in seeds[:args.control_seeds],
                                devices=devices)
        control = got.pop("control", None)
        out["program"][seed] = judged(got, rates[seed]["failed"])
        if control is not None:
            # No request of the control's fails: only its gaps can.
            out["control_fp8"][seed] = judged(control, 0)
        print(f"seed {seed}: program {out['program'][seed]} control "
              f"{out['control_fp8'].get(seed)}", file=sys.stderr, flush=True)
    separated = all(r["correct"] for r in out["program"].values()) \
        and not any(r["correct"] for r in out["control_fp8"].values())
    summary = {
        group: {k: {"min": min(r[k] for r in rows.values()),
                    "max": max(r[k] for r in rows.values())}
                for k, v in next(iter(rows.values())).items()
                if isinstance(v, float)}
        for group, rows in out.items() if rows}
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "device": harness.device_record(devices),
                      "memory_peak_bytes": peak, "windows": rates,
                      "limits": limits, "separated": separated,
                      "summary": summary, "readings": out}))
    return 0 if separated else 1


if __name__ == "__main__":
    sys.exit(main())
