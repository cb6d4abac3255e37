"""One run of one cell: ``python3 perf/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Reads the cell from ``BENCHMARK.json`` and the files under ``perf/``
that its names point to, runs it once in this process on the machine's
TPU, and prints the result as the last line of standard output. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result. ``--rehearse`` (tests only) shrinks every size and
runs the same control flow on whatever backend there is; it never
prints a result line and always exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, t_start: float | None = None) -> dict:
    """Run one cell and return the result line as a dict (not printed)."""
    from perf import harness, weights

    t_start = time.perf_counter() if t_start is None else t_start
    cell = harness.load_cell(name)
    if rehearse:
        from perf import rehearsal

        cell, sizes = rehearsal.shrink(cell)
    else:
        sizes = weights.load_sizes(cell["config"])
    try:
        from ddl_tpu.utils import compile_cache
    except ImportError as e:
        raise SystemExit(f"perf: the program is not here: {e}")
    if not rehearse:
        compile_cache.enable()
    devices = harness.find_devices(cell["chips"], rehearse)
    compiles = harness.CompileCounter()
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              rehearse=rehearse)
    module = f"perf.{cell['runner']}_runner"  # found by the cell's name for it
    if importlib.util.find_spec(module) is None:
        raise SystemExit(f"perf: unknown runner {cell['runner']!r}")
    runner = importlib.import_module(module)
    out = runner.run(cell, sizes, args, devices, t_start, compiles)
    device = harness.device_record(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    device.update(out["device_extra"])
    return {
        "correct": harness.judge(out["checked"]),
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": harness.metric_values(cell, trace, out["end_to_end"],
                                         out["per_layer"]),
        "device": device, "breakdown": out["breakdown"],
        "info": out["info"], "checked": out["checked"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from perf import harness

    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   rehearse=args.rehearse, t_start=T_START)
    if args.rehearse:
        print(f"perf: rehearsal of {args.workload} finished "
              f"(correct={res['correct']}); this is not a chip run and "
              "prints no result", file=sys.stderr)
        return 2
    harness.emit(correct=res["correct"], attempted=res["attempted"],
                 failed=res["failed"], metrics=res["metrics"],
                 device=res["device"], checked=res["checked"],
                 breakdown=res["breakdown"], extra=res["info"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
