"""Microbenchmark: hand-fused Pallas Adam vs the XLA-fused chain.

Measures one Adam update over a flat f32 vector (the ZeRO-1 shard update,
strategies/sync.py ``_adam_flat``) at shard sizes from the full model
(2.65M params, W=1) down to an 8-way shard — both paths under one jit,
each bracket closed by ``trainer.force`` (BASELINE.md measurement
integrity). Imports JAX, requires a TPU (the kernel is compiled, never
interpreted, here), runs, fails loudly.

Usage:
    python benchmarks/adam_kernel.py [--repeats 5] [--iters 100] [--json out]
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


def bench_path(n: int, fused: bool, iters: int, repeats: int) -> list[float]:
    """Per-repeat updates/sec for ``iters`` chained Adam updates in one jit."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.ops.pallas_adam import adam_flat_fused
    from ddl_tpu.train.trainer import force

    key = jax.random.PRNGKey(0)
    kp, km, kv, kg = jax.random.split(key, 4)
    p = jax.random.normal(kp, (n,), jnp.float32)
    m = jax.random.normal(km, (n,), jnp.float32)
    v = jnp.abs(jax.random.normal(kv, (n,), jnp.float32))
    g = jax.random.normal(kg, (n,), jnp.float32)

    def one(p, m, v, g, lr_t):
        if fused:
            return adam_flat_fused(p, m, v, g, lr_t)  # compiled (TPU)
        m2 = 0.9 * m + 0.1 * g
        v2 = 0.999 * v + 0.001 * g * g
        return p - lr_t * m2 / (jnp.sqrt(v2) + 1e-8), m2, v2

    @jax.jit
    def chain(p, m, v, g):
        def body(carry, i):
            p, m, v = carry
            lr_t = 1e-4 * (1.0 + 1e-6 * i.astype(jnp.float32))
            p, m, v = one(p, m, v, g, lr_t)
            return (p, m, v), ()

        (p, m, v), _ = jax.lax.scan(body, (p, m, v), jnp.arange(iters))
        return p, m, v

    p, m, v = chain(p, m, v, g)  # compile + warmup
    force((p, m, v))  # warmup
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        p, m, v = chain(p, m, v, g)
        force((p, m, v))
        out.append(iters / (time.perf_counter() - t0))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--json", dest="json_path", default=None)
    args = ap.parse_args()

    from ddl_tpu.utils import compile_cache

    compile_cache.enable()

    from ddl_tpu.parallel.mesh import device_record, require_tpu

    require_tpu()

    full = 2_656_010  # flagship param count (SURVEY.md §2.1)
    results = {}
    for n in (full, full // 4, -(-full // 8)):
        row = {}
        for fused in (False, True):
            vals = bench_path(n, fused, args.iters, args.repeats)
            row["pallas" if fused else "xla"] = {
                "best_updates_per_s": round(max(vals), 1),
                "median_updates_per_s": round(statistics.median(vals), 1),
            }
            print(f"[adam] n={n} {'pallas' if fused else 'xla':6s}: "
                  f"best {max(vals):,.0f} median "
                  f"{statistics.median(vals):,.0f} updates/s", file=sys.stderr)
        row["pallas_vs_xla"] = round(
            row["pallas"]["median_updates_per_s"]
            / row["xla"]["median_updates_per_s"], 3)
        results[n] = row
    payload = {"metric": "adam_update_fused_vs_xla",
               "device": device_record(),
               "iters_per_dispatch": args.iters,
               "results": results}
    print(json.dumps(payload))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(payload, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
