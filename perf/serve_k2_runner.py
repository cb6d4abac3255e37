"""The serving runner of the latent-attention routed-expert block: the
closed loop, the recorder and the end-to-end stamps of
``perf/serve_runner.py``, around this block's spec, weights and plain
reference (``perf/k2_weights.py``, ``perf/k2_reference.py``), as
``perf/serve_mimo_runner.py`` is around its family's.

The engine and scheduler are built as ``python -m ddl_tpu serve
--model-spec`` builds them and handed the benchmark's bf16 weights. A
program without the family's latent kind (the parent of the PR that
brought it) makes this module exit at once, non-zero.
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

try:
    from ddl_tpu.models import hybrid
    from ddl_tpu.models.hybrid import LATENT
except ImportError as e:  # the parent: no such kind
    raise SystemExit(f"perf: the program is not here: {e}")

from . import compare, harness, k2_weights as mw
from .serve_runner import (Recorder, drive, end_to_end, gap_numbers,
                           sample_finished)


def spec_of(s: mw.K2Sizes):
    return hybrid.HybridSpec(
        vocab=s.vocab, d_model=s.d_model, num_heads=s.num_heads,
        head_dim=s.nope_dim + s.rope_dim, v_head_dim=s.v_head_dim,
        q_lora_rank=s.q_lora, kv_lora_rank=s.kv_lora, nope_dim=s.nope_dim,
        rope_dim=s.rope_dim, rope_base_global=s.rope_base,
        rope_factor=s.rope_factor, rope_original=s.rope_original,
        rope_beta_fast=s.beta_fast, rope_beta_slow=s.beta_slow,
        rope_mscale_all_dim=s.mscale_all_dim, d_ff=s.d_ff,
        expert_ff=s.expert_ff, shared_ff=s.shared_ff,
        num_experts=s.router_width, experts_per_token=s.top_k,
        experts_held=s.experts_held, route_scale=s.route_scale,
        layer_kinds=(LATENT,) * s.num_layers, ffn_kinds=s.ffn_kinds,
        norm_eps=s.eps)


def build(cell: dict, sizes: mw.K2Sizes, seed: int):
    """Engine, scheduler and recorder, warmed up on one request per
    prefill bucket that the traffic can reach."""
    from ddl_tpu.serve import Request, Scheduler, ServeConfig, engine_cls

    traffic = cell["traffic_params"]
    cfg = ServeConfig(spec=spec_of(sizes), slots=traffic["clients"], seed=0,
                      **cell["engine"])
    engine = engine_cls(cfg.spec)(cfg, params=mw.make_weights(
        seed, sizes, cell["engine"]["compute_dtype"]))
    recorder = Recorder()
    recorder.engine_built_at = harness.now()
    scheduler = Scheduler(engine, eos_id=None, tracer=recorder)
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    chunk = cell["engine"].get("prefill_chunk") or hi
    bucket, lengths = engine.prefill_bucket(min(lo, chunk)), []
    while bucket < 2 * min(hi, chunk):
        lengths.append(min(bucket, hi, chunk))
        bucket *= 2
    lengths[-1] = hi  # the longest prompt whole: every decode bucket too
    scheduler.warmup([
        Request(id=i, prompt=np.zeros(n, np.int32),
                max_new_tokens=traffic["output"]["max"])
        for i, n in enumerate(lengths)])
    return engine, scheduler, recorder, Request


def reference_gaps(cell: dict, sizes, seed: int, served: list, *,
                   control: bool = False, devices=None) -> dict:
    """``serve_runner.reference_gaps`` with this block's weights: per
    served token the gap by which its logit lies below the reference's
    best; with ``control`` the same for the tokens the fp8 reference puts
    first at the same positions."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"{__package__}.{sizes.reference}")
    pad_to, n_at = cell["check"]["pad_to"], cell["traffic_params"]["output"]["max"]
    dev = (devices or jax.devices())[0]
    gap_fn = jax.jit(lambda logits, tok: jnp.max(logits, -1)
                     - jnp.take_along_axis(logits, tok[:, None], -1)[:, 0])
    program, lowered = [], []
    with jax.default_device(dev), \
            jax.default_matmul_precision("highest"):
        weights = mw.make_weights(seed, sizes,
                                  cell["engine"]["compute_dtype"])
        for prompt, out in served:
            seq = np.concatenate([prompt, out[:-1]])
            padded = np.zeros(-(-len(seq) // pad_to) * pad_to, np.int32)
            padded[:len(seq)] = seq
            at = np.full(n_at, len(seq) - 1, np.int32)
            at[:len(out)] = len(prompt) - 1 + np.arange(len(out))
            chosen = np.zeros(n_at, np.int32)
            chosen[:len(out)] = out
            logits = ref.served_logits(weights, jnp.asarray(padded),
                                       jnp.asarray(at), sizes=sizes,
                                       precision="fp32")
            program.append(np.asarray(
                gap_fn(logits, jnp.asarray(chosen)))[:len(out)])
            if control:
                low = ref.served_logits(weights, jnp.asarray(padded),
                                        jnp.asarray(at), sizes=sizes,
                                        precision="fp8")
                first = jnp.argmax(low, -1).astype(jnp.int32)
                lowered.append(np.asarray(gap_fn(logits, first))[:len(out)])
        del weights
    out = dict(gap_numbers(program), requests=len(served),
               tokens=int(sum(len(g) for g in program)))
    if control:
        out["control"] = gap_numbers(lowered)
    return out


def run(cell: dict, sizes, args, devices, t_start: float,
        compiles: harness.CompileCounter) -> dict:
    from . import readers

    if not isinstance(sizes, mw.K2Sizes):  # run.py read the shared keys
        sizes = mw.load_sizes(sizes.name)
    engine, scheduler, recorder, Request = build(cell, sizes, args.seed)
    compiled_before = compiles.count
    setup_s = harness.now() - t_start
    seconds = min(args.seconds, cell["trace_seconds"]) if args.trace \
        else args.seconds
    facts = drive(cell, sizes, args.seed, scheduler, recorder, Request,
                  seconds, cell["name"] if args.trace else None)
    compiled_inside = compiles.count - compiled_before
    peak = harness.memory_peak_bytes(devices)
    e2e = end_to_end(facts, recorder)
    served = sample_finished(cell, facts, args.seed)
    del engine, scheduler, Request
    gc.collect()

    ref = reference_gaps(cell, sizes, args.seed, served, devices=devices)
    numbers = {"logit_gap": ref["logit_gap"],
               "logit_gap_mean": ref["logit_gap_mean"],
               "logit_gap_p99": ref["logit_gap_p99"],
               "logit_gap_p90": ref["logit_gap_p90"],
               "requests_failed": e2e["failed"],
               "compiles_in_window": compiled_inside}
    checked = compare.checked_from(numbers, cell["check"]["limits"])
    per_layer, device_extra, breakdown = {}, {}, None
    if args.trace and facts["trace_path"]:
        ctx = {"cell": cell, "sizes": sizes, "facts": facts,
               "devices": devices, "trace_path": facts["trace_path"],
               "rehearse": args.rehearse}
        per_layer, device_extra, breakdown = readers.read_all(ctx)
        numbers["notes"] = ctx.get("notes")
    info = {k: e2e[k] for k in ("ttft_p95_ms", "itl_p50_ms", "tokens", "gaps",
                                "ttft_ms")}
    info.update(numbers=numbers, window_s=facts["seconds"],
                reference=ref, setup_compiles=compiled_before,
                setup_compile_s=compiles.seconds,
                requests_finished=len(facts["done"]),
                setup_engine_s=recorder.engine_built_at - t_start)
    return {"checked": checked, "attempted": e2e["attempted"],
            "failed": e2e["failed"],
            "end_to_end": dict(e2e, setup_s=setup_s),
            "per_layer": per_layer, "device_extra": device_extra,
            "breakdown": breakdown, "memory_peak_bytes": peak, "info": info}
