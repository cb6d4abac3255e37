"""Readings for the limits of ``correct``: not part of a benchmark run.

    python3 perf/limits.py --workload <name> --seeds 12 --control-seeds 3

One process, on the chip, at the cell's own sizes: the program's numbers
against the plain reference on many seeds (the lower readings), and the
control's and the planted faults' numbers on a few (the upper readings).
The control is the reference computed in float8 (``perf/reference.py``).
Faults, planted in the reference put in the program's place: half of
every batch left out with the mean taken over the rest (training); a
state left unchanged reads 1 by construction and needs no run. The last
line of standard output is one JSON object; ``PERF.md`` records what the
limits in ``perf/workloads/*.json`` were set from.
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


def train(cell, sizes, seeds, control_seeds, devices) -> dict:
    from perf import compare, train_runner as tr

    session = tr.build(cell, sizes, seeds[0])
    program = {}
    for i, seed in enumerate(seeds):
        if i:
            tr.reset(session, seed)
        program[seed] = tr.followed_steps(session)
        print(f"program seed {seed}: losses {program[seed]['losses']}",
              file=sys.stderr, flush=True)
    tr.free(session)
    del session
    gc.collect()
    out = {"program": {}, "control_fp8": {}, "fault_half_batch": {}}
    half = cell["traffic_params"]["batch"] // 2
    for seed in seeds:
        ref = tr.reference_readings(cell, sizes, seed, devices=devices)
        out["program"][seed] = compare.train_numbers(program[seed], ref)
        print(f"seed {seed}: {out['program'][seed]}", file=sys.stderr,
              flush=True)
        if seed in control_seeds:
            ctl = tr.reference_readings(cell, sizes, seed, precision="fp8",
                                        devices=devices)
            out["control_fp8"][seed] = compare.train_numbers(ctl, ref)
            flt = tr.reference_readings(cell, sizes, seed, keep_rows=half,
                                        devices=devices)
            out["fault_half_batch"][seed] = compare.train_numbers(flt, ref)
            print(f"  control {out['control_fp8'][seed]}\n  half batch "
                  f"{out['fault_half_batch'][seed]}", file=sys.stderr,
                  flush=True)
    return out


def serve(cell, sizes, seeds, control_seeds, devices, seconds) -> dict:
    import jax

    from perf import serve_runner as sr, weights as wts

    engine, scheduler, recorder, Request = sr.build(cell, sizes, seeds[0])
    served = {}
    for i, seed in enumerate(seeds):
        if i:
            fresh = wts.unstack(wts.make_weights(seed, sizes))
            engine.params = jax.tree.map(
                lambda new, was: jax.device_put(new, was.sharding), fresh,
                engine.params)
            engine.reset()
            recorder = sr.Recorder()
            scheduler = type(scheduler)(engine, eos_id=None, tracer=recorder)
        run = sr.drive(cell, sizes, seed, scheduler, recorder, Request,
                       seconds)
        served[seed] = sr.sample_finished(cell, run, seed)
        print(f"seed {seed}: {len(run['done'])} finished, sample of "
              f"{len(served[seed])}", file=sys.stderr, flush=True)
    del engine, scheduler, run
    gc.collect()
    out = {"program": {}, "control_fp8": {}}
    for seed in seeds:
        got = sr.reference_gaps(cell, sizes, seed, served[seed],
                                control=seed in control_seeds,
                                devices=devices)
        out["program"][seed] = {k: v for k, v in got.items()
                                if k != "control"}
        if seed in control_seeds:
            out["control_fp8"][seed] = got["control"]
        print(f"seed {seed}: {got}", file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2200000001)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="serve: the short window at the cell's own load")
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from perf import harness, weights
    from ddl_tpu.utils import compile_cache

    cell = harness.load_cell(args.workload)
    if args.rehearse:
        from perf import rehearsal

        cell, sizes = rehearsal.shrink(cell)
    else:
        sizes = weights.load_sizes(cell["config"])
        compile_cache.enable()
    devices = harness.find_devices(cell["chips"], args.rehearse)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    control = seeds[:args.control_seeds]
    if cell["runner"] == "train":
        out = train(cell, sizes, seeds, control, devices)
    else:
        out = serve(cell, sizes, seeds, control, devices, args.seconds)
    summary = {}
    for group, rows in out.items():
        keys = [k for k, v in next(iter(rows.values())).items()
                if isinstance(v, float)] if rows else []
        summary[group] = {k: {"min": min(r[k] for r in rows.values()),
                              "max": max(r[k] for r in rows.values())}
                          for k in keys}
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "device": harness.device_record(devices),
                      "summary": summary, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
