"""Time-to-target-accuracy benchmark (BASELINE.md north star).

The reference's only quality signal is eyeballing the accuracy prints
(mnist_sync/worker.py:71-75 — printed, never recorded; SURVEY.md §6). This
records it: ONE product-trainer run of the full-width flagship CNN on the
50k-image procedural set with the reference's hyperparameters and
``config.target_accuracy`` set, so the trainer itself stops at the first
eval that reaches the target — dropout streams advance across epochs
exactly as a normal multi-epoch run (no per-epoch restart), span programs
compile once, and the crossing is detected at ``--eval-every``-batch
granularity from the eval history.

Runs on whatever platform JAX selects and names it in the row; the
virtual CPU mesh is used only when ``--cpu`` asks for it, and a worker
count the platform cannot provide is an error.

Usage:
    python benchmarks/time_to_accuracy.py --variant single --target 0.99
    python benchmarks/time_to_accuracy.py --variant sync --workers 1 --bf16
    python benchmarks/time_to_accuracy.py --variant async --workers 8 --cpu
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Runnable as a script from anywhere: the package lives at the repo root,
# one level above this file.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _report(args, r, wall: float, variant: str, config: dict,
            extra: dict | None = None) -> int:
    """Shared report scaffolding for every TTA row (CNN and lm): crossing
    detection from the eval history, one JSON line to stdout, optional
    --json file. One place owns the schema so the rows can never drift."""
    crossing = next(
        ((e, b, a) for e, b, a in r.history if a >= args.target), None
    )
    from ddl_tpu.parallel.mesh import device_record

    result = {
        "metric": "time_to_accuracy",
        "variant": variant,
        "device": device_record(),
        "target": args.target,
        "reached": crossing is not None,
        "final_accuracy": round(r.final_accuracy, 4),
        "crossing": (
            {"epoch": crossing[0], "batch": crossing[1],
             "accuracy": round(crossing[2], 4)} if crossing else None
        ),
        "train_time_s": round(r.train_time_s, 2),
        "wall_time_s": round(wall, 2),
        "compile_time_s": round(r.compile_time_s, 2),
        **(extra or {}),
        "evals": [(e, b, round(a, 4)) for e, b, a in r.history],
        "config": config,
    }
    print(json.dumps(result))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(result, f, indent=2)
    return 0


def run_lm(args) -> int:
    """The long-context family's accuracy-as-oracle row: the decoder LM
    trains on the procedural copy task (data/lm.py — solvable only via
    attention ``seq_len/2 - 2`` positions back) until weighted next-token
    accuracy reaches the target. Same report shape as the CNN rows."""
    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.models.transformer import LMSpec
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer

    spec = LMSpec(vocab=64, d_model=128, num_heads=4, num_layers=2,
                  d_ff=512)
    cfg = SeqConfig(
        epochs=args.max_epochs,
        batch_size=args.batch,
        learning_rate=args.lr,
        eval_every=args.eval_every,
        num_workers=args.workers,
        compute_dtype="bfloat16" if args.bf16 else None,
        target_accuracy=args.target,
        spec=spec,
    )
    ds = synthesize_copy(num_train=args.train, num_test=args.test,
                         seq_len=args.seq_len, vocab=spec.vocab, seed=0)
    trainer = SeqTrainer(cfg, ds)
    t0 = time.perf_counter()
    r = trainer.train(log=lambda s: print(f"[tta] {s}", file=sys.stderr),
                      dispatch_timeout=args.dispatch_timeout)
    wall = time.perf_counter() - t0
    return _report(
        args, r, wall, "lm",
        config={
            "workers": args.workers, "batch": args.batch, "lr": args.lr,
            "bf16": args.bf16, "train_seqs": args.train,
            "seq_len": args.seq_len, "max_epochs": args.max_epochs,
            "eval_every": args.eval_every, "scheme": cfg.scheme,
        },
        extra={"tokens_per_sec": round(r.tokens_per_sec, 1)},
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="single",
                    choices=["single", "sync", "sync_sharding", "async",
                             "async_sharding", "lm"])
    ap.add_argument("--target", type=float, default=0.99)
    ap.add_argument("--max-epochs", type=int, default=20)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--num-ps", type=int, default=2)
    ap.add_argument("--layout", default="block")
    # Per-variant defaults (resolved below): the CNN rows use the
    # reference hyperparameters (batch 100, Adam 1e-4, 50k images); the
    # lm row uses its copy-task scale (batch 32 sequences, Adam 1e-3,
    # 2048 sequences of length --seq-len).
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--eval-every", type=int, default=None,
                    help="eval cadence in batches (async: rounds) — the "
                         "crossing-detection granularity")
    ap.add_argument("--train", type=int, default=None)
    ap.add_argument("--test", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=256,
                    help="lm only: sequence length of the copy task")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the virtual CPU mesh")
    ap.add_argument("--dispatch-timeout", type=float, default=0.0,
                    help="seconds before a hung device dispatch/fetch is "
                         "diagnosed as accelerator death (0 = wait forever)")
    ap.add_argument("--json", dest="json_path", default=None)
    args = ap.parse_args()

    from ddl_tpu.parallel.mesh import virtual_cpu_mesh
    from ddl_tpu.utils import compile_cache

    compile_cache.enable()
    if args.cpu:
        virtual_cpu_mesh(args.workers)
    else:
        import jax

        if len(jax.devices()) < args.workers:
            # Never a silent swap to the virtual mesh: the row names its
            # device, and a CPU row is asked for with --cpu.
            raise SystemExit(
                f"--workers {args.workers} needs {args.workers} devices, "
                f"the active platform ({jax.devices()[0].platform}) has "
                f"{len(jax.devices())}; pass --cpu for the virtual mesh"
            )

    lm = args.variant == "lm"
    args.batch = args.batch if args.batch is not None else (32 if lm else 100)
    args.lr = args.lr if args.lr is not None else (1e-3 if lm else 1e-4)
    args.eval_every = (args.eval_every if args.eval_every is not None
                       else (8 if lm else 100))
    args.train = args.train if args.train is not None else (2048 if lm else 50_000)
    args.test = args.test if args.test is not None else (256 if lm else 10_000)

    if lm:
        return run_lm(args)

    from ddl_tpu.data import load_mnist
    from ddl_tpu.train.config import TrainConfig

    cfg = TrainConfig(
        epochs=args.max_epochs,
        batch_size=args.batch,
        learning_rate=args.lr,
        eval_every=args.eval_every,
        num_workers=args.workers,
        num_ps=args.num_ps if "sharding" in args.variant else 1,
        layout=args.layout,
        compute_dtype="bfloat16" if args.bf16 else None,
        target_accuracy=args.target,
    )
    ds = load_mnist(path=None, synthetic_train=args.train,
                    synthetic_test=args.test, seed=0)
    if args.variant == "single":
        from ddl_tpu.train.trainer import SingleChipTrainer

        trainer = SingleChipTrainer(cfg, ds)
    elif args.variant.startswith("sync"):
        from ddl_tpu.strategies.sync import SyncTrainer

        trainer = SyncTrainer(cfg, ds)
    else:
        from ddl_tpu.strategies.async_ps import AsyncTrainer

        trainer = AsyncTrainer(cfg, ds)

    t0 = time.perf_counter()
    r = trainer.train(log=lambda s: print(f"[tta] {s}", file=sys.stderr),
                      dispatch_timeout=args.dispatch_timeout)
    wall = time.perf_counter() - t0
    return _report(
        args, r, wall, args.variant,
        config={
            "workers": args.workers, "batch": args.batch, "lr": args.lr,
            "bf16": args.bf16, "train_images": args.train,
            "max_epochs": args.max_epochs, "eval_every": args.eval_every,
            "num_ps": cfg.num_ps, "layout": cfg.layout,
        },
    )


if __name__ == "__main__":
    sys.exit(main())
