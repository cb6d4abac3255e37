"""The serving runner of the two-mixer block (linear-attention layers
over a state a slot, block-sparse attention layers over the page group):
the closed loop, the recorder and the end-to-end stamps of
``perf/serve_runner.py``, around this block's spec, weights and plain
reference (``perf/sala_weights.py``, ``perf/sala_reference.py``), as
``perf/serve_k2_runner.py`` is around its block's.

The engine and scheduler are built as ``python -m ddl_tpu serve
--model-spec`` builds them and handed the benchmark's bf16 weights. A
program without the family's linear and sparse kinds (the parent of the
PR that brought them) makes this module exit at once, non-zero.
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

try:
    from ddl_tpu.models import hybrid
    from ddl_tpu.models.hybrid import LINEAR, SPARSE
except ImportError as e:  # the parent: no such kinds
    raise SystemExit(f"perf: the program is not here: {e}")

import itertools

from . import compare, harness, sala_weights as mw, traffic as traffic_mod
from .serve_runner import (Recorder, end_to_end, gap_numbers,
                           sample_finished)


def spec_of(s: mw.SalaSizes):
    if s.kernel_size != 2 * s.kernel_stride:
        raise ValueError("the program's selector keeps means of kernel_stride "
                         "rows and halves the sum of two neighbours: "
                         "kernel_size must be twice kernel_stride")
    kinds = tuple(LINEAR if m == mw.LINEAR else SPARSE for m in s.mixers)
    return hybrid.HybridSpec(
        vocab=s.vocab, d_model=s.d_model, num_heads=s.num_heads,
        head_dim=s.head_dim, v_head_dim=s.head_dim,
        kv_heads_global=s.kv_heads, rope_base_global=s.rope_base,
        d_ff=s.d_ff, layer_kinds=kinds, ffn_kinds=(hybrid.DENSE,) * len(kinds),
        norm_eps=s.eps, embed_scale=s.scale_emb,
        residual_scale=s.residual_scale, logit_scale=s.logit_scale,
        sparse_block=s.block_size, sparse_stride=s.kernel_stride,
        sparse_topk=s.topk, sparse_init=s.init_blocks,
        sparse_local=s.window_size // s.block_size,
        sparse_dense_len=s.dense_len)


def build(cell: dict, sizes: mw.SalaSizes, seed: int):
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


def drive(cell: dict, sizes, seed: int, scheduler, recorder, Request,
          seconds: float, trace_name: str | None = None) -> dict:
    """``serve_runner.drive`` for a chunked prefill: the closed loop
    fills every slot and ticks until each of those first requests has its
    first token, and only then opens the window. There one tick prefills
    every waiting prompt whole; with ``prefill_chunk`` a tick prefills one
    chunk, so the first fill (some 680k prompt tokens here) is many ticks
    that no steady state holds. A request that ends during the fill is
    replaced at once, as in the window. The drain after the window lasts
    at most the cell's ``drain_seconds``: an output of 3,072 tokens at 20
    ms a tick does not end in that runner's 60 s."""
    import jax

    traffic = cell["traffic_params"]
    stream = traffic_mod.requests(traffic, seed, sizes.vocab)
    ids = itertools.count()
    sent: dict[int, object] = {}

    def submit():
        prompt, new = next(stream)
        req = Request(id=next(ids), prompt=prompt, max_new_tokens=new)
        recorder.prompt_len[req.id] = len(prompt)
        recorder.submitted[req.id] = harness.now()
        scheduler.submit(req)
        sent[req.id] = req

    def loop(go_on):
        seen = len(recorder.finished)
        while go_on():
            with jax.profiler.TraceAnnotation("tick"):
                scheduler.tick()
            with jax.profiler.TraceAnnotation("clients"):
                for _ in recorder.finished[seen:]:
                    submit()
                seen = len(recorder.finished)

    scheduler.begin()
    try:
        first = range(traffic["clients"])
        for _ in first:
            submit()
        loop(lambda: any(r not in recorder.token_times for r in first))
        first_in_window = next(ids)
        ids = itertools.count(first_in_window)
        calls_before = len(recorder.calls)
        t0 = harness.now()
        trace_path = None
        if trace_name:
            with harness.profiler_trace(trace_name) as found:
                loop(lambda: harness.now() < t0 + seconds)
            trace_path = found["xplane"]
        else:
            loop(lambda: harness.now() < t0 + seconds)
        t1 = harness.now()
        calls_traced = recorder.calls[calls_before:]
        while not scheduler.idle \
                and harness.now() < t1 + cell.get("drain_seconds", 60.0):
            scheduler.tick()
        done, _stats = scheduler.collect()
    finally:
        scheduler.release()
    return {"t0": t0, "t1": t1, "seconds": t1 - t0, "sent": sent,
            "done": done, "first_in_window": first_in_window,
            "traced_calls": calls_traced, "trace_path": trace_path}


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

    if not isinstance(sizes, mw.SalaSizes):  # run.py read the shared keys
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
    try:
        e2e = end_to_end(facts, recorder)
    except IndexError:  # a traced window too short for one submission
        e2e = dict.fromkeys(("ttft_p95_ms", "itl_p50_ms", "tokens", "gaps",
                             "ttft_ms"), None) | {"attempted": 0, "failed": 0}
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
                fill_s=facts["t0"] - t_start - setup_s,
                setup_engine_s=recorder.engine_built_at - t_start)
    return {"checked": checked, "attempted": e2e["attempted"],
            "failed": e2e["failed"],
            "end_to_end": dict(e2e, setup_s=setup_s),
            "per_layer": per_layer, "device_extra": device_extra,
            "breakdown": breakdown, "memory_peak_bytes": peak, "info": info}
