"""Decompose the train step's batch-independent overhead on the real chip.

The round-4 TPU sweep fits ``t(step) ~= a + c*batch`` with a ~= 2ms and
c ~= 2.3us/image — the fixed term alone caps batch-100 throughput at
~50k images/s and batch-2000 MFU at ~33%. This tool times jitted PIECES
of the step at two batch sizes to attribute ``a``:

  fwd        forward pass only (no dropout)
  fwd_patches  forward with the cin=1 first conv as a patches matmul
             (cnn._patches_block) — vs `fwd` decides the MXU-lane question
  fwd_tailmm forward with convs 3-4 (7x7/4x4 spatial) as patches matmuls
             — vs `fwd` decides whether deep MXU contractions beat the
             small-spatial conv kernels' fixed cost (round-4 verdict
             task 2; off-TPU smoke measured tail 2.8x faster already)
  fwd_allmm  every conv as a patches matmul
  fwd_drop   forward with dropout RNG (isolates threefry/bernoulli cost)
  grad       value_and_grad (fwd+bwd), no optimizer
  adam       Adam update alone on full-width grads (batch-independent)
  step       the full product train step (make_train_step)
  step_tailmm  the product step with --conv-matmul tail — the
             head-to-head that decides the recommended configuration
  span       a chunk_steps-long scan of the product step (make_epoch_chunk)
             at TWO span lengths — if per-step overhead falls with span
             length, the fixed term is per-DISPATCH (host launch
             latency), not per-step XLA work

Imports JAX, requires a TPU, runs, fails loudly. Prints one JSON dict.
Timing barriers follow bench.py (``trainer.force``), and each PIECE runs
its ``iters`` repetitions inside ONE on-device ``lax.scan`` whose carry
feeds a token into the next repetition's params: a data-dependent chain
cannot be reordered or overlapped, and a loop body with loop-invariant
inputs could be hoisted by XLA. The scan form also keeps per-dispatch
latency OUT of the piece times — the span section measures that term
separately.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Runnable as a script from anywhere: the package and bench.py live at the
# repo root, one level above this file.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import bench
from ddl_tpu.data import one_hot, synthesize
from ddl_tpu.models import cnn
from ddl_tpu.ops import adam_init, adam_update
from ddl_tpu.train.config import TrainConfig
from ddl_tpu.train.trainer import force, make_train_step, steps_scan


def timed(fn, args, *, iters: int, repeats: int) -> float:
    """Best-of-repeats seconds per repetition of ``fn(*args)``.

    One compiled program runs ``iters`` repetitions in a ``steps_scan``;
    the carry is a ~zero float token added to EVERY float leaf of every
    argument each repetition (params, opt state, grads, batch — and the
    repetition index is folded into raw PRNG-key leaves), recomputed as
    ``min(sum(EVERY output element), 0) * 1e-20``: perturbing all inputs
    leaves nothing loop-invariant to hoist (constant grads/opt let XLA
    hoist Adam's whole m'/v' chain; a constant key hoists the threefry
    generation), reducing over ALL leaves keeps every output live (a
    token built from one element lets XLA dead-code-eliminate the rest —
    observed collapsing the Adam piece 1000x), and the 1e-20 scale means
    the values are unperturbed at fp32/bf16 precision. Each timing
    bracket is ONE dispatch + one scalar fetch.
    """

    def body(tok, i):
        # Perturb EVERY float input (params, opt state, grads, batch) and
        # fold the repetition index into PRNG keys so no part of the piece
        # is loop-invariant: timing adam with constant grads/opt otherwise
        # lets XLA hoist the whole m'/v' chain out of the scan and time
        # only the params axpy, and a constant dropout key would hoist the
        # threefry/bernoulli generation the fwd_drop piece exists to
        # isolate (the product path varies its key per step via fold_in).
        def liven(a):
            if not hasattr(a, "dtype"):
                return a
            if jnp.issubdtype(a.dtype, jnp.floating):
                return a + tok.astype(a.dtype)
            if a.dtype == jnp.uint32 and a.shape == (2,):  # raw PRNG key
                return jax.random.fold_in(a, i)
            return a

        out = fn(*jax.tree.map(liven, args))
        s = sum(
            jnp.sum(leaf.astype(jnp.float32))
            for leaf in jax.tree_util.tree_leaves(out)
        )
        return jnp.minimum(s, 0.0) * jnp.float32(1e-20), None

    def prog(tok0):
        tok, _ = steps_scan(body, tok0, jnp.arange(iters), iters)
        return tok

    compiled = jax.jit(prog).lower(jnp.float32(0)).compile()
    tok = compiled(jnp.float32(0))
    force(tok)  # warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        tok = compiled(tok)
        force(tok)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, nargs="+", default=[100, 2000])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--spans", type=int, nargs="+", default=[1, 10, 30, 120],
                    help="span lengths for the per-dispatch-vs-per-step "
                         "attribution")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()

    from ddl_tpu.parallel.mesh import require_tpu
    from ddl_tpu.utils import compile_cache

    compile_cache.enable()
    require_tpu()
    params = cnn.init_params(jax.random.PRNGKey(0))
    opt = adam_init(params)
    rng = jax.random.PRNGKey(1)
    report: dict = {"platform": jax.default_backend(), "pieces": {}}

    def fwd(params, x):
        return cnn.apply_fn(params, x, compute_dtype=jnp.bfloat16)

    def fwd_patches(params, x):
        # First conv as patches-matmul (cnn._patches_block) — measured
        # against `fwd` to decide whether the cin=1 conv lowering wastes
        # MXU lanes in practice.
        return cnn.apply_fn(
            params, x, compute_dtype=jnp.bfloat16, first_conv_matmul=True
        )

    def fwd_tailmm(params, x):
        # Convs 3-4 (7x7 / 4x4 spatial) as patches-matmuls — the round-4
        # fit attributes the ~2ms batch-independent term to the small
        # conv kernels; this decides whether deep MXU matmuls beat the
        # conv lowering's fixed cost there (round-4 verdict task 2).
        return cnn.apply_fn(
            params, x, compute_dtype=jnp.bfloat16, conv_matmul="tail"
        )

    def fwd_allmm(params, x):
        return cnn.apply_fn(
            params, x, compute_dtype=jnp.bfloat16, conv_matmul="all"
        )

    def fwd_drop(params, x, rng):
        return cnn.apply_fn(
            params, x, dropout_rng=rng, compute_dtype=jnp.bfloat16
        )

    def gradp(params, x, y, rng):
        return jax.value_and_grad(cnn.loss_fn)(
            params, x, y, dropout_rng=rng, compute_dtype=jnp.bfloat16
        )

    def adam(params, opt, grads):
        return adam_update(params, opt, grads, lr=1e-4)

    grads_like = jax.tree.map(jnp.zeros_like, params)

    # Adam is batch-independent — time it ONCE, outside the batch loop.
    adam_t = timed(adam, (params, opt, grads_like), iters=args.iters,
                   repeats=args.repeats)
    report["adam_us"] = round(adam_t * 1e6, 1)
    print(f"[anatomy] adam (batch-independent): {adam_t*1e6:,.0f}us")

    for b in args.batches:
        x, y = synthesize(b, seed=0)
        xb = jnp.asarray(x, dtype=jnp.bfloat16)
        yb = jnp.asarray(one_hot(y))
        rows = {}
        for name, fn, a in (
            ("fwd", fwd, (params, xb)),
            ("fwd_patches", fwd_patches, (params, xb)),
            ("fwd_tailmm", fwd_tailmm, (params, xb)),
            ("fwd_allmm", fwd_allmm, (params, xb)),
            ("fwd_drop", fwd_drop, (params, xb, rng)),
            ("grad", gradp, (params, xb, yb, rng)),
        ):
            rows[name] = timed(fn, a, iters=args.iters, repeats=args.repeats)
        step = make_train_step(
            TrainConfig(batch_size=b, compute_dtype="bfloat16")
        )
        rows["step"] = timed(
            step, (params, opt, xb, yb, rng), iters=args.iters,
            repeats=args.repeats,
        )
        # The full product step with the tail convs as matmuls — the
        # head-to-head that decides whether --conv-matmul tail becomes
        # the recommended configuration.
        step_tail = make_train_step(
            TrainConfig(batch_size=b, compute_dtype="bfloat16",
                        conv_matmul="tail")
        )
        rows["step_tailmm"] = timed(
            step_tail, (params, opt, xb, yb, rng), iters=args.iters,
            repeats=args.repeats,
        )
        report["pieces"][b] = {k: round(v * 1e6, 1) for k, v in rows.items()}
        print(f"[anatomy] batch {b}: " + " ".join(
            f"{k}={v*1e6:,.0f}us" for k, v in rows.items()))

    # Span-length scaling at the smaller batch: per-step time vs k separates
    # per-dispatch overhead (falls ~1/k) from per-step XLA work (flat).
    # Measured through bench.bench_single — the SAME loop as the committed
    # bench rows (AOT compile, chained span dispatches, one closing
    # barrier), so this curve is directly comparable to bench.py's sweep
    # (k=30) and long_span (k=120) rows.
    b = args.batches[0]
    spans = {}
    for k in args.spans:
        vals = bench.bench_single(
            b, args.repeats, chunk_steps=k, rounds=max(1, 60 // k)
        )
        us_per_step = b / max(vals) * 1e6
        spans[k] = round(us_per_step, 1)
        print(f"[anatomy] span k={k} batch {b}: {us_per_step:,.0f}us/step")
    report["span_us_per_step"] = spans

    line = json.dumps(report)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
