"""Policy-search sweep on the digital twin (ISSUE 18).

Replays the scenario library's policy-search surfaces through the
cost-model engine (``ddl_tpu.serve.sim``) — no compiled programs, no
JAX device, virtual time instead of wall time — and sweeps a grid of
autoscale POLICIES over fleet role MIXES:

- **mixes** — ``colocated`` (the ``diurnal`` scenario: all-mixed
  replicas under sinusoidal day/night load) and ``disagg`` (the
  ``role_mix`` scenario: a 1:2 prefill/decode pattern with first-token
  page hand-offs).
- **policies** — ``static`` (min = max = the scenario fleet, the
  never-scales baseline), ``conservative`` (scale-out on sustained
  4.0 backlog/replica, slow drain) and ``aggressive`` (1.5
  backlog/replica, 1-tick sustain, fast drain, preemption on).

Every cell is one deterministic twin run: seeded traffic from the
scenario definition, the cost-model engine's virtual clock, the REAL
control plane (Router + FleetController + SloMonitor) making every
admission/shed/scale/preempt decision.  Per cell the table records the
decision rows a policy search ranks on:

- **goodput** — completed-ok fraction of offered requests
- per-class ``ok``/``shed`` and the router door-shed count
- the controller's **scale ledger** (scale_out / drain events, peak
  replicas) — the cost side of the goodput story
- **SLO attainment** — cumulative shed-burn (misses/total) and alert
  count per rule, read from the scenario's pinned SloMonitor rules
  (colocated mix; the role_mix scenario pins no rules)
- **ticks** — global scheduler ticks to drain the stream (the twin's
  duration row: wall clock means nothing on a virtual clock)
- **virtual time** per phase summed over sim engines — the twin's
  estimate of where fleet-seconds would go
- wall seconds (host cost of simulating the cell; excluded from the
  CI gate)

The artifact is a plain JSON document, flattened by
``obs.analyze load_metrics_flat`` into dotted numeric leaves — CI's
``twin-parity`` job regenerates it and gates the committed copy with::

    python -m ddl_tpu.obs.analyze compare \
        benchmarks/results_cpu/serve_twin_cpu.json fresh.json \
        --threshold 0.001 --ignore wall_s

(every leaf but ``wall_s`` is deterministic, so the gate is an
equality pin in practice).

    JAX_PLATFORMS=cpu python benchmarks/twin_bench.py \
        --json benchmarks/results_cpu/serve_twin_cpu.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rate-scale", type=float, default=3.0,
                    help="traffic multiplier over each scenario's base "
                         "rates (default 3.0 — enough load that the "
                         "scaling policies actually diverge)")
    ap.add_argument("--horizon", type=int, default=96,
                    help="arrival horizon in ticks (default 96)")
    ap.add_argument("--max-requests", type=int, default=600,
                    help="request cap per cell (default 600 — seconds "
                         "per cell on the cost model)")
    ap.add_argument("--max-replicas", type=int, default=6,
                    help="fleet cap for the scaling policies")
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--d-ff", type=int, default=128)
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    from ddl_tpu.utils import compile_cache

    compile_cache.enable()

    from ddl_tpu.models.transformer import LMSpec
    from ddl_tpu.obs import MetricRegistry
    from ddl_tpu.obs.goodput import fleet_summary
    from ddl_tpu.obs.slo import SloMonitor
    from ddl_tpu.serve import (
        AutoscaleConfig,
        Router,
        engine_kind,
        sim_engine_factory,
    )
    from ddl_tpu.serve.scenarios import DIURNAL, ROLE_MIX

    spec = LMSpec(vocab=args.vocab, d_model=args.d_model,
                  num_heads=args.heads, num_layers=args.layers,
                  d_ff=args.d_ff)

    mixes = (("colocated", DIURNAL), ("disagg", ROLE_MIX))

    def policies(scn):
        """The three-policy axis, sized to the scenario fleet. The
        static arm pins min = max = the scenario's base replicas with
        an unreachable backlog threshold — the controller exists (so a
        fault schedule could still deliver) but never scales."""
        base = scn.replicas
        return (
            ("static", AutoscaleConfig(
                max_replicas=base, min_replicas=base, preempt=False,
                backlog_per_replica=1e9)),
            ("conservative", AutoscaleConfig(
                max_replicas=args.max_replicas, min_replicas=base,
                backlog_per_replica=4.0, sustain_ticks=3, idle_ticks=8,
                preempt=False)),
            ("aggressive", AutoscaleConfig(
                max_replicas=args.max_replicas, min_replicas=base,
                backlog_per_replica=1.5, sustain_ticks=1, idle_ticks=4,
                preempt=True)),
        )

    def run_cell(scn, acfg):
        reqs = scn.build_traffic(
            args.vocab, horizon=args.horizon,
            max_requests=args.max_requests, rate_scale=args.rate_scale,
        )
        reg = MetricRegistry()
        mon = SloMonitor(scn.slo_rules(), reg) \
            if scn.slo_rule_classes else None
        router = Router(
            scn.router_config(spec, engine_factory=sim_engine_factory()),
            registry=reg, slo_monitor=mon,
            controller=scn.make_controller(autoscale=acfg),
        )
        t0 = time.perf_counter()
        done, rstats = router.run(reqs)  # the twin compiles nothing
        wall = time.perf_counter() - t0

        summary = rstats.summary()
        requests = sum(c["requests"] for c in summary["per_class"].values())
        ok = sum(c["ok"] for c in summary["per_class"].values())
        shed = sum(c["shed"] for c in summary["per_class"].values())
        vt: dict[str, float] = {}
        for eng in router.engines:
            if eng is None or engine_kind(eng) != "sim":
                continue  # drained slots leave a None; be loud-proof
            for phase, s in eng.virtual_time().items():
                vt[phase] = vt.get(phase, 0.0) + s
        fleet = fleet_summary(reg)
        row = {
            "requests": requests,
            "ok": ok,
            "shed": shed,
            "goodput": round(ok / requests, 4) if requests else 0.0,
            "router_sheds": summary["router_sheds"],
            "per_class": {
                c: {"requests": d["requests"], "ok": d["ok"],
                    "shed": d["shed"]}
                for c, d in summary["per_class"].items()
            },
            "replicas_peak": summary["replicas"],
            "ticks": summary["ticks"],
            "scale_events": _event_counts(router),
            "replicas_active": fleet.get("replicas_active"),
            "virtual_time_s": {p: round(s, 4) for p, s in sorted(vt.items())},
            "wall_s": round(wall, 3),
        }
        if mon is not None:
            row["slo"] = {
                r.name: {
                    "misses": mon.cumulative(r.name)[0],
                    "total": mon.cumulative(r.name)[1],
                    "alerts": mon.alerts(r.name),
                }
                for r in scn.slo_rules()
            }
        return row

    def _event_counts(router):
        ctrl = router.controller
        out = {"scale_out": 0, "drain": 0, "preempt": 0}
        if ctrl is None:
            return out
        for _, kind, _ in ctrl.events:
            if kind in out:
                out[kind] += 1
        return out

    grid: dict[str, dict] = {}
    for mix_label, scn in mixes:
        grid[mix_label] = {}
        for pol_label, acfg in policies(scn):
            row = run_cell(scn, acfg)
            grid[mix_label][pol_label] = row
            print(f"[twin_bench] {mix_label}/{pol_label}: goodput "
                  f"{row['goodput']:.3f} ok {row['ok']}/{row['requests']} "
                  f"shed {row['shed']} scale_out "
                  f"{row['scale_events']['scale_out']} "
                  f"({row['wall_s']}s)", file=sys.stderr)

    # -- the per-policy table ------------------------------------------------
    hdr = (f"{'mix':<10} {'policy':<13} {'goodput':>8} {'ok':>6} "
           f"{'shed':>5} {'door':>5} {'out':>4} {'drain':>6} "
           f"{'preempt':>8} {'alerts':>7} {'ticks':>6} {'vtime_s':>8}")
    print(hdr)
    print("-" * len(hdr))
    for mix_label in grid:
        for pol_label, row in grid[mix_label].items():
            alerts = sum(v["alerts"] for v in row.get("slo", {}).values())
            ev = row["scale_events"]
            print(f"{mix_label:<10} {pol_label:<13} "
                  f"{row['goodput']:>8.3f} {row['ok']:>6} "
                  f"{row['shed']:>5} {row['router_sheds']:>5} "
                  f"{ev['scale_out']:>4} {ev['drain']:>6} "
                  f"{ev['preempt']:>8} {alerts:>7} {row['ticks']:>6} "
                  f"{row['virtual_time_s'].get('total', 0.0):>8.3f}")

    out = {
        "metric": "twin_policy_sweep_goodput",
        "engine_kind": "sim",
        "scale": {
            "rate_scale": args.rate_scale,
            "horizon": args.horizon,
            "max_requests": args.max_requests,
            "max_replicas": args.max_replicas,
        },
        "grid": grid,
    }
    line = json.dumps(out)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
