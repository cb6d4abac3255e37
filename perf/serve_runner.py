"""The serving runner: ``Scheduler.submit`` / ``tick`` over the paged
``InferenceEngine``, driven by a closed loop of clients on the wall clock.

The engine and scheduler are built as ``python -m ddl_tpu serve`` builds
them and handed the benchmark's weights. Every time the scheduler tells
its tracer of a token (``first_token``, ``decode_tick``) or a completion,
the recorder below stamps the benchmark's own clock: the end-to-end
metrics come from those stamps and from the clients' own submit times,
not from anything the program computed. After the window has drained
and the engine is freed, the plain reference runs over a sample of the
finished requests with their served tokens.
"""

from __future__ import annotations

import gc
import importlib
import itertools

import numpy as np

from . import compare, harness, traffic as traffic_mod, weights as wts


class Recorder:
    """The scheduler's tracer, by its calling convention (``event``,
    ``complete``, truthiness). Stamps by the benchmark's clock at the
    moment of the call."""

    def __init__(self):
        self.submitted: dict[int, float] = {}   # set by the clients
        self.prompt_len: dict[int, int] = {}
        self.token_times: dict[int, list[float]] = {}
        self.length: dict[int, int] = {}        # rows resident in the cache
        self.finished: list[tuple[int, str]] = []
        self.calls: list[dict] = []             # device calls, in order
        self.engine_built_at = 0.0              # a set-up mark, for info

    def __bool__(self) -> bool:
        return True

    def event(self, name: str, t=None, **attrs) -> None:
        now = harness.now()
        if name == "first_token":
            req = attrs["req"]
            self.token_times[req] = [now]
            self.length[req] = self.prompt_len[req]
        elif name == "complete":
            self.finished.append((attrs["req"], attrs.get("status", "ok")))
        elif name in ("shed", "deadline_exceeded"):
            self.finished.append((attrs["req"], name))

    def complete(self, name: str, t0, t1, **attrs) -> None:
        now = harness.now()
        if name == "prefill_chunk":
            self.calls.append({"kind": "prefill", "tokens": attrs["n"],
                               "req": attrs["req"], "t": now})
        elif name == "decode_tick":
            contexts = []
            for req in attrs["reqs"]:
                self.token_times[req].append(now)
                self.length[req] += 1
                contexts.append(self.length[req])
            self.calls.append({"kind": "decode", "contexts": contexts,
                               "resident_tokens": sum(contexts), "t": now})


def build(cell: dict, sizes: wts.Sizes, seed: int):
    """Engine, scheduler and recorder, warmed up on one request per
    prefill bucket that the traffic can reach."""
    from ddl_tpu.models.transformer import LMSpec
    from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig

    spec = LMSpec(vocab=sizes.vocab, d_model=sizes.d_model,
                  num_heads=sizes.num_heads, num_layers=sizes.num_layers,
                  d_ff=sizes.d_ff, rope_base=sizes.rope_base)
    traffic = cell["traffic_params"]
    cfg = ServeConfig(spec=spec, slots=traffic["clients"], seed=0,
                      **cell["engine"])
    engine = InferenceEngine(cfg, params=wts.unstack(
        wts.make_weights(seed, sizes)))
    recorder = Recorder()
    recorder.engine_built_at = harness.now()
    scheduler = Scheduler(engine, eos_id=None, tracer=recorder)
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    bucket, lengths = engine.prefill_bucket(lo), []
    while bucket < 2 * hi:  # one request a prefill bucket the traffic reaches
        lengths.append(min(bucket, hi))
        bucket *= 2
    scheduler.warmup([
        Request(id=i, prompt=np.zeros(n, np.int32),
                max_new_tokens=traffic["output"]["max"])
        for i, n in enumerate(lengths)])
    return engine, scheduler, recorder, Request


def drive(cell: dict, sizes, seed: int, scheduler, recorder, Request,
          seconds: float, trace_name: str | None = None) -> dict:
    """The closed loop: fill every slot, open the window once the first
    tick has prefilled them, replace each finished request at once, stop
    sending when the window closes, drain."""
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

    def loop(until):
        seen = len(recorder.finished)
        while harness.now() < until:
            with jax.profiler.TraceAnnotation("tick"):
                scheduler.tick()
            with jax.profiler.TraceAnnotation("clients"):
                for _ in recorder.finished[seen:]:
                    submit()
                seen = len(recorder.finished)

    scheduler.begin()
    try:
        for _ in range(traffic["clients"]):
            submit()
        scheduler.tick()
        first_in_window = next(ids)
        ids = itertools.count(first_in_window)
        calls_before = len(recorder.calls)
        t0 = harness.now()
        trace_path = None
        if trace_name:
            with harness.profiler_trace(trace_name) as found:
                loop(t0 + seconds)
            trace_path = found["xplane"]
        else:
            loop(t0 + seconds)
        t1 = harness.now()
        calls_traced = recorder.calls[calls_before:]
        while not scheduler.idle and harness.now() < t1 + 60.0:
            scheduler.tick()
        done, _stats = scheduler.collect()
    finally:
        scheduler.release()
    return {"t0": t0, "t1": t1, "seconds": t1 - t0, "sent": sent,
            "done": done, "first_in_window": first_in_window,
            "traced_calls": calls_traced, "trace_path": trace_path}


def end_to_end(run: dict, recorder: Recorder) -> dict:
    """The window's metrics from the benchmark's own stamps."""
    t0, t1, length = run["t0"], run["t1"], run["seconds"]
    tokens = sum(1 for times in recorder.token_times.values()
                 for t in times if t0 <= t <= t1)
    inside = [r for r in run["sent"] if r >= run["first_in_window"]]
    ttft, gaps, failed = [], [], 0
    for req in inside:
        times = recorder.token_times.get(req)
        done = run["done"].get(req)
        if done is None or done.status != "ok" \
                or len(done.tokens) != run["sent"][req].max_new_tokens:
            failed += 1
        if not times:
            ttft.append(length)
            continue
        ttft.append(times[0] - recorder.submitted[req])
        gaps.extend(b - a for a, b in zip(times, times[1:]))
    return {"serve_tokens_per_s": tokens / length,
            "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * harness.percentile(gaps, 95),
            "attempted": len(inside), "failed": failed,
            "ttft_p50_ms": 1e3 * harness.median(ttft),
            "itl_p50_ms": 1e3 * harness.median(gaps),
            "tokens": tokens, "gaps": len(gaps),
            "ttft_ms": [round(1e3 * t, 2) for t in sorted(ttft)]}


def sample_finished(cell: dict, run: dict, seed: int) -> list:
    """The finished requests the reference reads: the longest, and a
    draw from the seed."""
    ok = sorted(r for r, c in run["done"].items()
                if r >= 0 and c.status == "ok" and c.tokens)
    if not ok:
        return []
    size = lambda r: run["done"][r].prompt_len + len(run["done"][r].tokens)
    longest = max(ok, key=size)
    rest = [r for r in ok if r != longest]
    rng = wts.host_rng(seed, 3)
    k = min(len(rest), cell["check"]["requests"] - 1)
    picked = list(rng.choice(rest, size=k, replace=False)) if k else []
    return [(run["sent"][r].prompt, np.asarray(run["done"][r].tokens, np.int32))
            for r in [longest] + sorted(int(r) for r in picked)]


def reference_gaps(cell: dict, sizes, seed: int, served: list, *,
                   control: bool = False, devices=None) -> dict:
    """Run the reference once over each prompt with its served tokens.
    Per served token, the gap by which its logit lies below the
    reference's best; the numbers of :func:`gap_numbers` over all of
    them. With ``control`` also, under ``control``, the same numbers for
    the tokens the fp8 reference puts first at the same positions."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"{__package__}.{sizes.reference}")

    pad_to, n_at = cell["check"]["pad_to"], cell["traffic_params"]["output"]["max"]
    dev = (devices or jax.devices())[0]
    gap_fn = jax.jit(lambda logits, tok: jnp.max(logits, -1)
                     - jnp.take_along_axis(logits, tok[:, None], -1)[:, 0])
    program, lowered = [], []
    with jax.default_device(dev):
        weights = wts.make_weights(seed, sizes)
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


def gap_numbers(gaps: list) -> dict:
    """The widest, the mean and two quantiles of the served tokens' gaps;
    ``None`` for each where nothing was served."""
    if not gaps:
        return dict.fromkeys(("logit_gap", "logit_gap_mean", "logit_gap_p99",
                              "logit_gap_p90"))
    flat = np.concatenate(gaps)
    return {"logit_gap": float(flat.max()),
            "logit_gap_mean": float(flat.mean()),
            "logit_gap_p99": float(np.quantile(flat, 0.99)),
            "logit_gap_p90": float(np.quantile(flat, 0.9))}


def run(cell: dict, sizes: wts.Sizes, args, devices, t_start: float,
        compiles: harness.CompileCounter) -> dict:
    from . import readers

    engine, scheduler, recorder, Request = build(cell, sizes, args.seed)
    compiled_before = compiles.count
    # Set-up ends where the clients start: the first tick, which fills
    # every slot, is load and not set-up, but it is outside the window.
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
