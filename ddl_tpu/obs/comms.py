"""Live communication plane (ISSUE 20): the collective ledger, the ICI
roofline, and comms-vs-compute attribution.

The source paper's sync-vs-async question is a communication story, and
the grounding papers judge their systems by exactly these ledgers —
2004.13336's weight-update rewrite by bytes-per-step, 2204.06514's
pjit/TPUv4 scaling by compute-vs-ICI roofline attribution. Until now the
repo's only byte evidence was the OFFLINE audit in
``benchmarks/collective_bytes.py``; this module makes the same parser a
library surface and feeds it from the points where programs are already
built, so the byte story is live telemetry, not a separate tool run:

- :func:`collective_ops` — THE collective-op HLO parser (the benchmark
  now imports it; one parser, no drift), extended with replica-group /
  source-target-pair recovery so bytes can be attributed to MESH AXES.
- :func:`program_text` — the optimized-HLO fetch, module-level and
  monkeypatchable ON PURPOSE: ``as_text()`` costs real milliseconds per
  program, so every caller gates it behind a live registry exactly like
  the falsy-tracer clock reads, and the off-path pin installs a bomb
  here to prove registry-less runs never fetch (tests/test_comms.py).
- :func:`publish_program_ledger` — one static ledger per DISTINCT
  compiled program: ``collective_bytes{kind=,program=}`` /
  ``collective_axis_bytes{axis=,program=}`` gauges and
  ``collective_ops_total{kind=,program=}`` counters, plus a
  ``collective_bytes_total{program=}`` sum that exists even at 0 so a
  collective-free program still proves it published.
- :data:`ICI_BW_BY_KIND` / :func:`ici_bw_per_device` — the comms twin
  of ``obs.cost.PEAK_FLOPS_BY_KIND``: per-device-kind nominal link
  bandwidth with a CPU fallback and an ``--ici-bw`` override.
- :func:`roofline` / :func:`fit_roofline` — the two-roofline step-time
  model ``t = max(flops/peak, bytes/bw)``: the live gauges publish the
  model next to ``train_mfu`` every span, and the fit falsifies it
  against measured step times across topologies
  (``benchmarks/collective_bytes.py`` rows, ``analyze comms``) the way
  ``pipeline_bubble.py`` falsified the bubble model.

Wiring (all gated on a live registry — no registry, no HLO fetch, no
parsing, no gauges, compiled programs unchanged by construction):

- trainers (``strategies/seq.py``, ``train/trainer.py``): the span/eval
  compiles where ``record_compile`` already fires publish the ledger,
  and the per-span metrics block publishes ``comms_bytes_per_step``,
  ``comms_time_model_s`` / ``compute_time_model_s`` /
  ``step_time_model_s``, ``comms_fraction`` and
  ``step_bound{bound=compute|comms}`` next to ``train_mfu``.
- serve (``serve/engine.py`` + ``serve/scheduler.py``): the scheduler
  attaches ``engine.ledger_hook`` beside the existing ``compile_hook``;
  each cached program then AOT-compiles at its first real call,
  publishes its ledger once, and runs the ``Compiled`` executable from
  then on (engine ``_LedgeredProgram`` docstring for why this is the
  only order that avoids compiling twice).
- host-side byte plane: ``handoff_bytes_total{path=preempt|requeue|
  disagg}`` counters on the scheduler/router registries, priced by the
  ``serve.cache.kv_row_bytes`` oracle (``engine.handoff_bytes``).
"""

from __future__ import annotations

import itertools
import re

import numpy as np

# -- the parser (lifted from benchmarks/collective_bytes.py) ------------------

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
                "pred": 1, "s8": 1, "u8": 1}

_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")

# ``-start``: the TPU compiler schedules collective-permute (and, where
# it overlaps them, all-gather / all-reduce) as async start/done pairs;
# the start line carries the groups, so it is the one counted.
_OP_PAT = re.compile(
    r"=\s*(.*?)\s(" + "|".join(_COLLECTIVES) + r")(-start)?\("
)
_SHAPE_PAT = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# replica_groups={{0,2},{1,3}} — the explicit form this backend emits.
_GROUPS_PAT = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
# replica_groups=[2,2]<=[4] (iota form, optionally [2,2]<=[2,2]T(1,0)):
# arange over the source dims, transposed, reshaped to [groups, size].
_IOTA_PAT = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
# collective-permute carries source_target_pairs instead of groups.
_PAIRS_PAT = re.compile(r"source_target_pairs=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")


def _parse_groups(line: str):
    """Device groups of one HLO collective line: a list of id lists, or
    ``None`` when the line carries no group attribute (HLO semantics:
    one group of every participant — the caller resolves "every" from
    its mesh). ``collective-permute`` pairs are unioned into their
    connected components (a ring permute over an axis connects exactly
    that axis's members, so the component set matches the axis
    partition the same way a replica-group set does)."""
    m = _IOTA_PAT.search(line)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        src = [int(d) for d in m.group(2).split(",")]
        ids = np.arange(int(np.prod(src)), dtype=np.int64).reshape(src)
        if m.group(3):
            ids = ids.transpose([int(d) for d in m.group(3).split(",")])
        return [list(map(int, row)) for row in ids.reshape(dims)]
    m = _GROUPS_PAT.search(line)
    if m:
        return [[int(x) for x in g.split(",") if x.strip() != ""]
                for g in re.findall(r"\{([^}]*)\}", m.group(1))]
    m = _PAIRS_PAT.search(line)
    if m:
        pairs = [tuple(int(x) for x in g.split(","))
                 for g in re.findall(r"\{([^}]*)\}", m.group(1))]
        parent: dict[int, int] = {}

        def find(a: int) -> int:
            parent.setdefault(a, a)
            while parent[a] != a:
                parent[a] = parent[parent[a]]
                a = parent[a]
            return a

        for a, b in pairs:
            parent[find(a)] = find(b)
        comps: dict[int, list[int]] = {}
        for a in parent:
            comps.setdefault(find(a), []).append(a)
        return [sorted(v) for v in comps.values()]
    return None


def collective_ops(hlo_text: str) -> list[dict]:
    """Parse collective ops + result shapes out of optimized HLO text.

    Handles tuple-shaped (fused) results — ``= (f32[5882], f32[])
    all-reduce(...)`` counts EVERY member shape, so a fused full-vector
    all-reduce can never hide behind a scalar sibling (the audit's whole
    point is catching exactly that regression) — and the async
    ``<op>-start`` spelling a TPU program uses (reported under the
    plain op name; the ``-done`` half is not a second op). Each row also carries
    ``groups`` — the op's device groups (replica_groups, iota or
    permute pairs; ``None`` when the line names no groups) — the raw
    material :func:`publish_program_ledger` turns into per-mesh-axis
    attribution."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_PAT.search(line)
        if not m:
            continue
        result_txt, op = m.group(1), m.group(2)
        members = _SHAPE_PAT.findall(result_txt)
        if m.group(3) and op in ("all-gather", "collective-permute"):
            # An async start returns (operand, result[, contexts]):
            # only the result is traffic.
            members = members[1:2]
        shapes = []
        total_bytes = 0
        for dtype, dims in members:
            shape = [int(d) for d in dims.split(",") if d] if dims else []
            elems = 1
            for d in shape:
                elems *= d
            shapes.append({"dtype": dtype, "shape": shape,
                           "elems": elems})
            total_bytes += elems * _DTYPE_BYTES.get(dtype, 4)
        out.append({
            "op": op,
            "dtype": shapes[0]["dtype"] if shapes else "?",
            "shape": [s["shape"] for s in shapes] if len(shapes) > 1
                     else (shapes[0]["shape"] if shapes else []),
            "max_elems": max((s["elems"] for s in shapes), default=0),
            "bytes": total_bytes,
            "groups": _parse_groups(line),
        })
    return out


def program_text(compiled) -> str:
    """Optimized-HLO text of an AOT-``Compiled`` program. The ONE
    fetch every ledger goes through — module-level so the off-path pin
    can monkeypatch a bomb here and prove registry-less runs never pay
    the (real, milliseconds-per-program) ``as_text()`` cost."""
    return compiled.as_text()


# -- mesh-axis attribution ----------------------------------------------------


def mesh_axis_partitions(mesh) -> dict:
    """``{frozenset-of-frozenset device groups: axis label}`` for every
    nonempty subset of ``mesh``'s axes: the subset's groups are the
    partition of global device ids that agree on every OTHER axis's
    coordinate — exactly the replica_groups a collective over those
    axes names. Labels join axis names with ``x`` in mesh order;
    size-1-axis collisions keep the SMALLEST subset's label (an op
    over ``(dp,)`` on a ``dp=2, tp=1`` mesh is a dp op)."""
    ids = np.vectorize(lambda d: d.id)(np.asarray(mesh.devices))
    names = tuple(mesh.axis_names)
    n = ids.ndim
    out: dict = {}
    for r in range(1, n + 1):
        for subset in itertools.combinations(range(n), r):
            other = [a for a in range(n) if a not in subset]
            flat = ids.transpose([*other, *subset]).reshape(
                -1, int(np.prod([ids.shape[a] for a in subset],
                                dtype=np.int64))
            )
            part = frozenset(frozenset(int(x) for x in row) for row in flat)
            out.setdefault(part, "x".join(names[a] for a in subset))
    return out


def _axis_of(groups, partitions: dict, all_ids: frozenset | None) -> str:
    """Axis label of one op's device groups (``unknown`` when the
    group set matches no axis subset of the mesh — or when no mesh was
    given). A group-less op (``groups=None``) spans every participant:
    resolved as the full-device partition."""
    if not partitions:
        return "unknown"
    if groups is None:
        if all_ids is None:
            return "unknown"
        part = frozenset((all_ids,))
    else:
        part = frozenset(frozenset(g) for g in groups)
    return partitions.get(part, "unknown")


# -- the ledger ---------------------------------------------------------------


def publish_program_ledger(registry, hlo_text: str, *, program: str,
                           mesh=None) -> dict:
    """Publish ONE compiled program's static collective ledger on
    ``registry`` and return its summary. Gauges, not counters, for the
    byte surfaces — the ledger is a property of the program, set once
    at build (re-publishing the same program is idempotent by
    construction); ``collective_ops_total`` counts ops per (collective
    kind, program) so re-compiles of the same program label are visible
    as increments, exactly like ``xla_compiles_total``.

    ``program`` is the ``kind[key]`` label the compile-activity hook
    already uses (``train_span[3]``, ``prefill[16]``, ``decode[2]``...)
    so the two surfaces join on it. ``mesh`` (optional) turns each op's
    recovered device groups into a mesh-axis label
    (:func:`mesh_axis_partitions`); without it — or when the groups
    match no axis subset — bytes land under ``axis="unknown"``."""
    ops = collective_ops(hlo_text)
    partitions = mesh_axis_partitions(mesh) if mesh is not None else {}
    all_ids = None
    if mesh is not None:
        all_ids = frozenset(
            int(d.id) for d in np.asarray(mesh.devices).flat
        )
    by_kind: dict[str, int] = {}
    by_axis: dict[str, int] = {}
    for o in ops:
        by_kind[o["op"]] = by_kind.get(o["op"], 0) + o["bytes"]
        axis = _axis_of(o["groups"], partitions, all_ids)
        by_axis[axis] = by_axis.get(axis, 0) + o["bytes"]
        registry.counter(
            "collective_ops_total",
            "collective ops per compiled program (kind=collective op)",
        ).inc(1, kind=o["op"], program=program)
    g = registry.gauge(
        "collective_bytes",
        "static per-program collective result bytes by collective kind",
    )
    for k, b in sorted(by_kind.items()):
        g.set(b, kind=k, program=program)
    ga = registry.gauge(
        "collective_axis_bytes",
        "static per-program collective bytes by mesh axis",
    )
    for a, b in sorted(by_axis.items()):
        ga.set(b, axis=a, program=program)
    total = sum(by_kind.values())
    # Present even at 0: a collective-free program (a single-device
    # span, a page write) still proves its ledger published.
    registry.gauge(
        "collective_bytes_total",
        "static per-program collective result bytes, all kinds",
    ).set(total, program=program)
    return {"program": program, "total_bytes": total, "ops": len(ops),
            "by_kind": by_kind, "by_axis": by_axis}


# -- ICI bandwidth table (the comms twin of cost.PEAK_FLOPS_BY_KIND) ----------

# Nominal per-chip aggregate ICI bandwidth (bytes/s) by device-kind
# substring (lowercase), most specific first — vendor-published
# interconnect figures converted to bytes/s. Anchors for the roofline
# model, not measurements: --ici-bw pins a real number (the fitted
# value `fit_roofline` recovers from measured rows is the honest one).
ICI_BW_BY_KIND: tuple[tuple[str, float], ...] = (
    ("v5p", 6.0e11),
    # A v5e reports device_kind "TPU v5 lite"; 1,600 Gbit/s per chip
    # (Google Cloud "TPU v5e" page) = 2.0e11 bytes/s.
    ("v5 lite", 2.0e11),
    ("v5e", 2.0e11),
    ("v5litepod", 2.0e11),
    ("v4", 3.0e11),
    ("v3", 1.4e11),
    ("v2", 1.0e11),
)

# Nominal host fallback (~10 GB/s, memcpy-through-shared-memory order):
# keeps the comms roofline defined on CPU smoke runs. An anchor, not a
# measurement — pass --ici-bw to pin a real number.
CPU_NOMINAL_ICI_BW = 1e10


def ici_bw_per_device(device=None, override: float | None = None) -> float:
    """Nominal interconnect bytes/s for one device: ``override`` wins;
    else the ``device_kind`` table; else, for a CPU device (or none),
    the CPU nominal fallback. An ACCELERATOR kind the table doesn't
    know raises ``cost.UnknownDeviceKind`` — anchoring its comms
    roofline to the CPU nominal would model every step as hopelessly
    comms-bound (the exact failure mode ``cost.peak_flops_per_device``
    guards for MFU)."""
    if override is not None:
        if override <= 0:
            raise ValueError(
                f"ici bw override must be > 0, got {override}"
            )
        return float(override)
    kind = ""
    if device is not None:
        kind = str(getattr(device, "device_kind", "")).lower()
    for key, bw in ICI_BW_BY_KIND:
        if key in kind:
            return bw
    platform = str(getattr(device, "platform", "cpu")).lower()
    if platform != "cpu":
        from .cost import UnknownDeviceKind

        raise UnknownDeviceKind(
            f"unknown accelerator device_kind {kind!r}: no interconnect "
            "row in obs.comms.ICI_BW_BY_KIND — add one with its source, "
            "or pass --ici-bw (ici_bw=) with the chip's real link "
            "bandwidth"
        )
    return CPU_NOMINAL_ICI_BW


# -- the two-roofline step-time model -----------------------------------------


def roofline(flops: float, comm_bytes: float, n_devices: int,
             peak_per_device: float, bw_per_device: float) -> dict:
    """The two-roofline step-time model of one step:
    ``compute = flops / (n_devices * peak)``, ``comms = bytes / bw``
    (the parser's bytes are already per-device result bytes — each
    device's share of the program's collective traffic), and the
    modeled step is their MAX (perfect-overlap assumption — the
    falsifiable claim :func:`fit_roofline` tests). ``comms_fraction``
    is the no-overlap share ``comms / (compute + comms)`` — a live
    dial, not the binding verdict; ``bound`` is the verdict."""
    compute_s = (flops / (n_devices * peak_per_device)
                 if n_devices >= 1 and peak_per_device > 0 else 0.0)
    comms_s = comm_bytes / bw_per_device if bw_per_device > 0 else 0.0
    denom = compute_s + comms_s
    return {
        "compute_time_model_s": compute_s,
        "comms_time_model_s": comms_s,
        "step_time_model_s": max(compute_s, comms_s),
        "comms_fraction": comms_s / denom if denom > 0 else 0.0,
        "bound": "comms" if comms_s > compute_s else "compute",
    }


def fit_roofline(rows, iters: int = 25) -> dict | None:
    """Fit the two parameters of ``t = max(f * inv_peak, b * inv_bw)``
    to measured rows ``{"flops": f, "bytes": b, "measured_s": t}`` —
    the falsification harness: if the two-roofline model is right, ONE
    (inv_peak, inv_bw) pair must explain every topology's measured step
    time at once (the way ``pipeline_bubble.py``'s one alpha had to
    explain every (pp, M) cell).

    Alternating assignment + per-side least squares: classify each row
    by which term currently binds, refit that side's slope on its rows,
    repeat to a fixed point. Returns the fitted peaks, per-row model
    times and relative errors, and ``max_rel_err`` — the headline
    number ``analyze comms`` prints. ``None`` with fewer than 2 usable
    rows (a 1-row fit is unfalsifiable)."""
    rows = [r for r in rows
            if r.get("measured_s") and r["measured_s"] > 0
            and r.get("flops") and r["flops"] > 0]
    if len(rows) < 2:
        return None
    f = np.array([float(r["flops"]) for r in rows])
    b = np.array([float(r.get("bytes") or 0.0) for r in rows])
    t = np.array([float(r["measured_s"]) for r in rows])
    inv_peak = float(np.median(t / f))
    with np.errstate(divide="ignore"):
        ratios = np.where(b > 0, t / np.where(b > 0, b, 1.0), np.inf)
    finite = ratios[np.isfinite(ratios)]
    inv_bw = float(np.median(finite)) if finite.size else 0.0
    for _ in range(iters):
        comp = f * inv_peak >= b * inv_bw
        new_peak, new_bw = inv_peak, inv_bw
        if comp.any():
            new_peak = float((t[comp] * f[comp]).sum()
                             / (f[comp] * f[comp]).sum())
        comms = ~comp & (b > 0)
        if comms.any():
            new_bw = float((t[comms] * b[comms]).sum()
                           / (b[comms] * b[comms]).sum())
        if new_peak == inv_peak and new_bw == inv_bw:
            break
        inv_peak, inv_bw = new_peak, new_bw
    model = np.maximum(f * inv_peak, b * inv_bw)
    rel = np.abs(model - t) / t
    return {
        "inv_peak_s_per_flop": inv_peak,
        "inv_bw_s_per_byte": inv_bw,
        "fitted_peak_flops": 1.0 / inv_peak if inv_peak > 0 else 0.0,
        "fitted_bw_bytes_per_s": 1.0 / inv_bw if inv_bw > 0 else 0.0,
        "model_s": [float(x) for x in model],
        "rel_err": [float(x) for x in rel],
        "max_rel_err": float(rel.max()),
    }


__all__ = [
    "CPU_NOMINAL_ICI_BW",
    "ICI_BW_BY_KIND",
    "collective_ops",
    "fit_roofline",
    "ici_bw_per_device",
    "mesh_axis_partitions",
    "program_text",
    "publish_program_ledger",
    "roofline",
]
