"""Scaling benchmark: images/sec for each strategy at 1..N devices.

Feeds BASELINE.md (target: sync_sharding >= 70% linear scaling 1->8 chips).
On the CPU virtual mesh this measures *algorithmic* overhead (collective
count, serve-loop cost), not ICI bandwidth — TPU numbers come from running
the same script on real hardware.

Usage:
    python benchmarks/scaling.py [--devices 8] [--steps 30] [--batch 800]
                                 [--cpu] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

# Runnable as a script from anywhere: the package lives at the repo root,
# one level above this file.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from ddl_tpu.parallel.mesh import virtual_cpu_mesh  # noqa: E402


def bench_strategy(variant: str, workers: int, steps: int, batch: int) -> float:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddl_tpu.data import one_hot, synthesize
    from ddl_tpu.parallel.mesh import DP_AXIS, make_mesh
    from ddl_tpu.train.config import TrainConfig

    if variant == "lm_ring":
        return bench_lm_ring(workers, steps, batch)
    if variant == "lm_ring_tp2":
        # sp x tp on the SAME device count as the lm_ring row (skipped
        # below at W=1 — tp=2 needs at least 2 devices).
        return bench_lm_ring(workers, steps, batch, tp=2)

    mesh = make_mesh(workers)
    x_np, y_np = synthesize(batch, seed=0)
    y_np = one_hot(y_np)
    cfg = TrainConfig(
        num_workers=workers,
        batch_size=batch,
        keep_prob=1.0,
        num_ps=workers if "shard" in variant else 1,
        layout="flat" if variant == "sharded_flat" else
               ("zigzag" if "greedy" in variant else "block"),
    )
    from ddl_tpu.models import cnn

    params = cnn.init_params(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)

    if variant.startswith("async"):
        from ddl_tpu.strategies.async_ps import (
            async_schedule, async_state_init, make_async_round,
            serve_layout_for,
        )
        from ddl_tpu.strategies.sync import resolve_layout

        if variant == "async_replicated":
            # The replicated-scan serve (the semantic oracle) kept as a
            # measured comparison row; "async" measures the PRODUCT serve
            # routing via the same helper AsyncTrainer uses.
            layout = resolve_layout(cfg, workers)
        else:
            layout = serve_layout_for(cfg, workers)
        state = async_state_init(cfg, mesh, layout, params)
        run = make_async_round(cfg, mesh, layout)
        R = 4  # rounds per call
        per = batch // workers
        xs = jnp.asarray(x_np.reshape(1, workers, per, -1).repeat(R, 0))
        ys = jnp.asarray(y_np.reshape(1, workers, per, -1).repeat(R, 0))
        rngs = jnp.stack([jax.random.fold_in(rng, r) for r in range(R)])
        scheds = jnp.asarray(async_schedule(0, workers, R))
        state, ps, _ = run(state, xs, ys, rngs, scheds)  # compile
        jax.block_until_ready(ps)
        t0 = time.perf_counter()
        calls = max(1, steps // R)
        for _ in range(calls):
            state, ps, _ = run(state, xs, ys, rngs, scheds)
        jax.block_until_ready(ps)
        dt = time.perf_counter() - t0
        return calls * R * batch / dt

    from ddl_tpu.strategies.sync import (
        make_dp_step, make_sharded_step, resolve_layout, sharded_adam_init,
    )
    from ddl_tpu.ops import adam_init

    data_sh = NamedSharding(mesh, P(DP_AXIS))
    x = jax.device_put(jnp.asarray(x_np), data_sh)
    y = jax.device_put(jnp.asarray(y_np), data_sh)
    layout = resolve_layout(cfg, workers)
    if layout is None:
        step = make_dp_step(cfg, mesh)
        opt = jax.device_put(adam_init(params), NamedSharding(mesh, P()))
    else:
        step = make_sharded_step(cfg, mesh, layout)
        opt = sharded_adam_init(mesh, layout)
    p = jax.device_put(params, NamedSharding(mesh, P()))
    p, opt, _ = step(p, opt, x, y, rng)  # compile
    jax.block_until_ready(p)
    t0 = time.perf_counter()
    for i in range(steps):
        p, opt, _ = step(p, opt, x, y, jax.random.fold_in(rng, i))
    jax.block_until_ready(p)
    dt = time.perf_counter() - t0
    return steps * batch / dt


def bench_lm_ring(workers: int, steps: int, batch: int,
                  tp: int = 1) -> float:
    """Sequence-parallel LM retention row: tokens/sec through the product
    ``SeqTrainer`` span program (ring attention over sp), sequence length
    fixed at 256 so the W sweep varies only the SHARDING — on the 1-core
    proxy ideal is constant tokens/s and the retained fraction is the
    ring/psum program overhead (same reading as the CNN rows). ``batch``
    is interpreted as a token budget per step (sequences = batch // 256).
    ``tp > 1`` splits the same ``workers`` devices into a [1, W/tp, tp]
    mesh — the sp×tp composition vs pure sp at EQUAL device count, i.e.
    the algorithmic cost of the Megatron completion psums."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.models.transformer import LMSpec
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer
    from ddl_tpu.train.trainer import force

    T = 256
    nseq = max(2, batch // T)
    k = 4  # steps per dispatched span
    spec = LMSpec(vocab=64, d_model=64, num_heads=4, num_layers=2, d_ff=256)
    ds = synthesize_copy(num_train=nseq * k, num_test=nseq, seq_len=T,
                         vocab=64, seed=0)
    tr = SeqTrainer(
        SeqConfig(num_workers=workers // tp, scheme="ring", batch_size=nseq,
                  tensor_parallel=tp, spec=spec),
        ds,
    )
    xs = tr.stage_batches(ds.tokens, k, nseq)
    ys = tr.stage_batches(ds.targets, k, nseq)
    ws = tr.stage_batches(ds.weights, k, nseq)
    params, opt = tr.params, tr.opt_state
    fn = tr.span_program(k).lower(params, opt, xs, ys, ws, jnp.int32(0)).compile()
    params, opt, loss = fn(params, opt, xs, ys, ws, jnp.int32(0))  # warmup
    force((params, opt, loss))
    calls = max(1, steps // k)
    t0 = time.perf_counter()
    for _ in range(calls):
        params, opt, loss = fn(params, opt, xs, ys, ws, jnp.int32(0))
    force((params, opt, loss))
    dt = time.perf_counter() - t0
    return calls * k * nseq * T / dt


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=800)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the virtual CPU mesh (default: the active "
                         "platform, which must have --devices devices)")
    ap.add_argument("--json", default=None)
    ap.add_argument("--repeats", type=int, default=3,
                    help="measurements per cell; the record keeps best "
                         "(capability) AND median (expected) — a single "
                         "shot on the shared 1-core host carries ~40%% "
                         "noise spikes (round-5: a one-shot lm_ring W=8 "
                         "read 59%% retention where best-of-3 reads ~120%%)")
    ap.add_argument("--variants", default=None,
                    help="comma-separated subset of "
                         "sync_dp,sharded_flat,sharded_greedy,async,"
                         "async_replicated,lm_ring,lm_ring_tp2 "
                         "(default: all but async_replicated)")
    args = ap.parse_args()

    from ddl_tpu.utils import compile_cache

    compile_cache.enable()
    if args.cpu:
        virtual_cpu_mesh(args.devices)

    import jax

    if len(jax.devices()) < args.devices:
        raise SystemExit(
            f"--devices {args.devices}: the active platform "
            f"({jax.devices()[0].platform}) has {len(jax.devices())}; "
            "pass --cpu for the virtual mesh"
        )

    results: dict[str, dict[int, float]] = {}
    medians: dict[str, dict[int, float]] = {}
    widths = [w for w in (1, 2, 4, 8) if w <= args.devices]
    known = ("sync_dp", "sharded_flat", "sharded_greedy", "async",
             "async_replicated", "lm_ring", "lm_ring_tp2")
    variants = (
        args.variants.split(",")
        if args.variants else list(known[:4]) + ["lm_ring", "lm_ring_tp2"]
    )
    bad = [v for v in variants if v not in known]
    if bad:
        raise SystemExit(
            f"unknown variant(s) {bad}; choose from {', '.join(known)}"
        )
    for variant in variants:
        results[variant] = {}
        for w in widths:
            # W=1 is measured once as the shared CNN baseline (sync_dp)
            # — except lm_ring, whose units are tokens/s and whose
            # retention baseline is its own W=1 (degenerate ring).
            if variant not in ("sync_dp", "lm_ring") and w == 1:
                continue
            vals = [bench_strategy(variant, w, args.steps, args.batch)
                    for _ in range(max(1, args.repeats))]
            ips = max(vals)
            results[variant][w] = round(ips, 1)
            medians.setdefault(variant, {})[w] = round(
                statistics.median(vals), 1
            )
            unit = "tok/s" if variant.startswith("lm_ring") else "img/s"
            print(f"{variant:15s} W={w}: best {ips:10.1f} {unit} "
                  f"median {statistics.median(vals):10.1f} "
                  f"(raw {[round(v) for v in vals]})", flush=True)

    base = results.get("sync_dp", {}).get(1)
    platform = jax.devices()[0].platform
    # Virtual mesh: every "device" shares the host cores, so ideal strong
    # scaling is CONSTANT img/s at fixed global batch; the honest proxy
    # metric is the throughput retained vs W=1 — the algorithmic overhead
    # of the collectives / serve machinery. On real chips the efficiency
    # form applies. lm_ring measures tokens/s and retains vs its OWN W=1;
    # a subset run without the matching W=1 baseline reports raw
    # throughput only (the loop skips it).
    for variant, per_w in results.items():
        # lm rows retain vs the LM's own W=1 (tokens/s units); the tp
        # composition row shares lm_ring's baseline — same model, same
        # token budget, equal device counts per column.
        b = (results.get("lm_ring", {}).get(1)
             if variant.startswith("lm_ring") else base)
        if b is None:
            continue
        for w, ips in per_w.items():
            if platform == "cpu":
                print(f"{variant:15s} W={w}: {ips / b:6.1%} of W=1 "
                      "throughput retained (1-core proxy; 100% = zero "
                      "algorithmic overhead)")
            else:
                print(f"{variant:15s} W={w}: scaling efficiency "
                      f"{ips / (b * w):5.1%}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"platform": platform,
                       "batch": args.batch, "steps": args.steps,
                       "repeats": max(1, args.repeats),
                       "results": results,
                       "results_median": medians}, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
