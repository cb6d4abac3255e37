"""What every runner shares: files by name, the look for a chip, the
compile counter, the profiler bracket, memory, and the result line."""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".perf_out")  # listed in .gitignore


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """A cell's own file joined with its entry in ``BENCHMARK.json``,
    its traffic file and the per-layer metrics that list it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"perf: no workload {name!r} in BENCHMARK.json")
    cell = load_json("workloads", name)
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"perf: {name}: {key} differs between "
                             "BENCHMARK.json and perf/workloads")
    cell["traffic_params"] = load_json("traffic", cell["traffic"])
    cell["end_to_end"] = [
        m for m in manifest["end_to_end"]
        if name in m.get("workloads", [name])]
    cell["per_layer"] = [
        dict(load_json("metrics", m["name"]), **m)
        for m in manifest["per_layer"] if name in m.get("workloads", [name])]
    return cell


def find_devices(chips: int, rehearse: bool):
    """The devices the cell runs on. Without a TPU, or with fewer chips
    than the cell asks for, the command fails; a rehearsal (tests only)
    takes whatever backend there is."""
    import jax

    devices = jax.devices()
    if not rehearse and devices[0].platform != "tpu":
        raise SystemExit(f"perf: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"perf: the cell needs {chips} devices, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    """The device as JAX reports it; ``count`` is of all it reports."""
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices) -> int | None:
    """The peak on the fullest chip, as the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compilations (reads from the persistent cache
    included) through JAX's monitoring events. The measured window must
    see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


@contextlib.contextmanager
def profiler_trace(cell_name: str):
    """Bracket a traced window; yields a dict that holds the path of the
    ``.xplane.pb`` once the bracket has closed."""
    import jax

    out = os.path.join(OUT, cell_name, "trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    found: dict = {"dir": out}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the host spans are the benchmark's own
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("perf_window"):
            yield found
    finally:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        found["xplane"] = max(files, key=os.path.getmtime) if files else None


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return float(ordered[int(rank) - 1])


def median(values) -> float:
    return float(statistics.median(values))


def judge(checked: dict) -> bool:
    """``checked`` maps a short name to ``{"value", "limit"}``; correct
    when every value is a number at or under its limit."""
    return all(e["value"] is not None and e["value"] <= e["limit"]
               for e in checked.values())  # a nan is not <= anything


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checked: dict, breakdown: dict | None = None,
         extra: dict | None = None) -> None:
    """The result line (last on stdout) and the numbers compared (last
    on stderr, and last in the line)."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line["info"] = extra
    line["checked"] = checked
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    for name, entry in checked.items():
        print(f"checked {name}: value {entry['value']} limit "
              f"{entry['limit']}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)


def metric_values(cell: dict, trace_on: bool, end_to_end: dict,
                  per_layer: dict) -> dict:
    """The cell's metrics as the result line wants them: the end-to-end
    ones without a trace, the per-layer ones with. A per-layer reader
    that found nothing is left out."""
    if not trace_on:
        return {m["name"]: {"value": end_to_end[m["name"]], "unit": m["unit"]}
                for m in cell["end_to_end"]}
    return {m["name"]: {"value": per_layer[m["name"]], "unit": m["unit"]}
            for m in cell["per_layer"] if per_layer.get(m["name"]) is not None}


def now() -> float:
    return time.perf_counter()
