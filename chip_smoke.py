"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the three product paths once, through the classes the
CLI constructs, at the full width of the models the repo ships, and checks
what comes out by the repo's own means:

    python chip_smoke.py              # one chip: cnn_train, lm_train, serve,
                                      #           fused_adam
    python chip_smoke.py --chips 4    # four chips: cnn_4chip, lm_4chip and
                                      #           their one-device comparisons

It needs a TPU: with any other platform it exits non-zero and prints no
result. It never selects a platform itself. Every failed check is fatal.
Each phase prints one JSON line (compile seconds, run seconds, the values
checked — set-up information, not results); the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearse`` shrinks every size so the control flow of all phases can be
run on the CPU backend (Pallas kernels in interpret mode) before chip time
is spent; it never relaxes the platform check, so a rehearsal always ends
non-zero without the result line:

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=4 python chip_smoke.py --chips 4 --rehearse

Weights and data are random, made from ``--seed``. The compile cache lives
where ``ddl_tpu.utils.compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything ``--rehearse`` shrinks. Step counts and span structure
    are the same in both, so a rehearsal runs the real control flow."""

    conv_channels: tuple
    fc_sizes: tuple
    cnn_batch: int
    cnn_test: int
    lm: dict  # LMSpec fields
    lm_seq: int
    lm_seq_4chip: int
    lm_batch: int
    page_size: int
    capacity: int
    prompt_min: int
    prompt_max: int
    new_tokens: int


FULL = Sizes(
    conv_channels=(32, 64, 128, 256), fc_sizes=(1024, 512),  # 2,656,010 params
    cnn_batch=100, cnn_test=2000,
    # The widest LM the repo's own benches use (benchmarks/lm_bench.py).
    lm=dict(vocab=256, d_model=512, num_heads=8, num_layers=4, d_ff=2048),
    lm_seq=1024, lm_seq_4chip=2048, lm_batch=8,
    page_size=64, capacity=256, prompt_min=16, prompt_max=128, new_tokens=32,
)
TINY = Sizes(
    conv_channels=(4, 8, 8, 8), fc_sizes=(32, 16),
    cnn_batch=16, cnn_test=64,
    lm=dict(vocab=32, d_model=32, num_heads=2, num_layers=2, d_ff=64),
    lm_seq=32, lm_seq_4chip=64, lm_batch=8,
    page_size=8, capacity=64, prompt_min=4, prompt_max=24, new_tokens=8,
)

CNN_STEPS = 30      # eval_every=10 -> spans of 1, 10 and 9 steps
PAIR_STEPS = 10     # the keep_prob=1.0 equivalence pairs
LM_STEPS = 8
LM_STEPS_4CHIP = 4
SERVE_SLOTS = 4
SERVE_PROMPTS = 8
# tests/test_sync_strategies.py: sync DP / ZeRO-1 == one device, fp32.
SYNC_ATOL = 2e-5
# The same equivalence across chips, where the reduction order differs.
# Adam's m/(sqrt(v)+eps) turns reduction-order noise in a near-zero
# gradient into a whole +-lr step, so after PAIR_STEPS steps a few of the
# 2.66M parameters stand up to PAIR_STEPS*lr apart even in exact fp32
# (CPU: 11 parameters beyond SYNC_ATOL, 4.4e-5 at most), and max-abs is
# the wrong yardstick. What must agree is the update as a whole,
# |run - W=1| / |W=1 - init| over all parameters, and the loss the runs
# reach on the same batch; a wrong shard or a missing or doubled
# reduction moves both by O(1). The comparison runs at matmul precision
# "highest": at the TPU's default (bf16 passes, chosen per shape) W=4
# and W=1 already part by 5e-2 of the update in ten steps while their
# losses agree to 4e-5 — arithmetic, not placement, and not what this
# check is for. At "highest" they are 1.1e-4 of the update apart, one
# parameter beyond SYNC_ATOL (four v5e chips, PR 21).
UPDATE_RTOL = 1e-2
LOSS_RTOL = 1e-4
# tests/test_lm.py: ring == full, final loss.
RING_RTOL = 1e-3
# xla einsum softmax vs flash kernel, first-step loss, bf16 compute
# (3e-7 apart on the chip, PR 21).
FLASH_RTOL = 1e-3
# Causal ring vs einsum attention gradients, bf16 inputs (relative L2).
RING_GRAD_RTOL = 5e-2
# tests/test_pallas_adam.py: fused kernel vs the XLA chain.
ADAM_ATOL = 2e-7
# Paged vs slot-major decode where greedy tokens split at a near tie:
# that step's fp32 logits must still agree this closely.
SERVE_LOGIT_ATOL = 5e-2


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _quiet(_msg: str) -> None:
    pass


class CompileMeter:
    """Seconds JAX spent in backend compilation (cache reads included)
    and persistent-cache hits/misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _count(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(name: str, fn, meter: CompileMeter, *args) -> None:
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    checked = fn(*args)
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    print(json.dumps({
        "phase": name,
        "compile_s": round(c1 - c0, 3),
        "run_s": round(wall - (c1 - c0), 3),
        "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
        "checked": checked,
    }), flush=True)


def _max_abs_diff(a: dict, b: dict) -> float:
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(a[k], np.float64)
                                   - np.asarray(b[k], np.float64))))
               for k in a)


def _relative_l2(a: list, b: list, origin: list | None = None) -> float:
    """|a - b| / |b - origin| over lists of arrays (origin: zeros)."""
    import numpy as np

    f64 = lambda x: np.asarray(x, np.float64)
    origin = origin or [0.0] * len(b)
    num = sum(float(((f64(x) - f64(y)) ** 2).sum()) for x, y in zip(a, b))
    den = sum(float(((f64(y) - f64(o)) ** 2).sum())
              for y, o in zip(b, origin))
    return (num / den) ** 0.5


def _distinct_devices(arr) -> int:
    return len({s.device for s in arr.addressable_shards})


def _collectives(compiled) -> list[dict]:
    from ddl_tpu.obs import comms

    return comms.collective_ops(comms.program_text(compiled))


def _kinds(ops: list[dict]) -> list[str]:
    return sorted({op["op"] for op in ops})


def _sharded_update_form(tag: str, lowered, ops: list[dict],
                         flat_elems: int) -> str:
    """How a ZeRO-1 program moves its gradients. It asks for a
    reduce-scatter and an all-gather; the TPU compiler may keep the
    reduce-scatter or rewrite it as an all-reduce of the whole flat
    vector followed by a slice (it does on a 2x2 v5e). Either is the
    sharded update; anything else is not."""
    check("reduce_scatter" in lowered.as_text(),
          f"{tag}: the program does not ask for a reduce-scatter")
    check("all-gather" in _kinds(ops), f"{tag}: no all-gather: {_kinds(ops)}")
    if "reduce-scatter" in _kinds(ops):
        return "reduce-scatter"
    check(any(op["op"] == "all-reduce" and op["max_elems"] >= flat_elems
              for op in ops),
          f"{tag}: gradients move by neither a reduce-scatter nor a "
          f"whole-vector all-reduce: {_kinds(ops)}")
    return "all-reduce of the whole vector + slice"


# -- phase 1: the CNN trainers ----------------------------------------------


def _cnn_data(sz: Sizes, steps: int, batch: int, seed: int):
    from ddl_tpu.data import load_mnist

    return load_mnist(path=None, synthetic_train=steps * batch,
                      synthetic_test=sz.cnn_test, seed=seed)


def _cnn_cfg(sz: Sizes, seed: int, **kw):
    from ddl_tpu.train.config import TrainConfig

    return TrainConfig(epochs=1, seed=seed, conv_channels=sz.conv_channels,
                       fc_sizes=sz.fc_sizes, **kw)


def _time_barriers(trainer, ds, sz: Sizes, peak: float) -> dict:
    """One CNN_STEPS-step span timed to ``jax.block_until_ready`` and to
    a host fetch of its loss. If block_until_ready returned before the
    device finished, it would read shorter than the fetch — and shorter
    than the span's FLOPs at the chip's peak allow."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.data import one_hot
    from ddl_tpu.obs import cost
    from ddl_tpu.train.trainer import staging_dtype

    cfg = trainer.config
    n = CNN_STEPS * cfg.batch_size
    xs = jnp.asarray(np.asarray(ds.x_train[:n]).reshape(
        CNN_STEPS, cfg.batch_size, -1), dtype=staging_dtype(cfg))
    ys = jnp.asarray(one_hot(ds.y_train[:n]).reshape(
        CNN_STEPS, cfg.batch_size, -1))
    zero = jnp.int32(0)
    key = trainer.dropout_key
    params = jax.tree.map(jnp.copy, trainer.params)
    opt = jax.tree.map(jnp.copy, trainer.opt_state)
    span = trainer._chunk_fn(CNN_STEPS).lower(
        params, opt, xs, ys, zero, zero, key).compile()
    params, opt, loss = span(params, opt, xs, ys, zero, zero, key)
    jax.block_until_ready((params, opt, loss))
    block_s, fetch_s = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        params, opt, loss = span(params, opt, xs, ys, zero, zero, key)
        jax.block_until_ready((params, opt, loss))
        block_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        params, opt, loss = span(params, opt, xs, ys, zero, zero, key)
        float(loss)
        fetch_s.append(time.perf_counter() - t0)
        jax.block_until_ready((params, opt))
    floor_s = CNN_STEPS * cost.cnn_train_step_flops(
        cfg.batch_size, cfg.conv_channels, cfg.fc_sizes) / peak
    b, f = min(block_s), min(fetch_s)
    check(b >= floor_s and f >= floor_s,
          f"a {CNN_STEPS}-step span returned in {b:.6f}s (block) / {f:.6f}s "
          f"(fetch), under its FLOPs at peak ({floor_s:.6f}s): not a barrier")
    check(abs(b - f) <= 0.25 * max(b, f),
          f"block_until_ready ({b:.6f}s) and host fetch ({f:.6f}s) disagree "
          "on the same span")
    return {"block_until_ready_s": block_s, "host_fetch_s": fetch_s,
            "flops_floor_s": floor_s}


def _cnn_pair(sz: Sizes, seed: int, ds, **dtype_kw):
    """SingleChipTrainer vs SyncTrainer W=1, no dropout, PAIR_STEPS steps
    from the same init: max |param difference|."""
    from ddl_tpu.strategies.sync import SyncTrainer
    from ddl_tpu.train.trainer import SingleChipTrainer

    pair_ds = dataclasses.replace(
        ds, x_train=ds.x_train[:PAIR_STEPS * sz.cnn_batch],
        y_train=ds.y_train[:PAIR_STEPS * sz.cnn_batch])
    kw = dict(batch_size=sz.cnn_batch, keep_prob=1.0, eval_every=0,
              **dtype_kw)
    a = SingleChipTrainer(_cnn_cfg(sz, seed, **kw), pair_ds).train(log=_quiet)
    b = SyncTrainer(_cnn_cfg(sz, seed, num_workers=1, **kw),
                    pair_ds).train(log=_quiet)
    return _max_abs_diff(a.params, b.params)


def phase_cnn_train(sz: Sizes, seed: int) -> dict:
    import jax
    import numpy as np

    from ddl_tpu.data import one_hot
    from ddl_tpu.models import cnn
    from ddl_tpu.obs import comms, cost
    from ddl_tpu.strategies.sync import SyncTrainer
    from ddl_tpu.train.trainer import SingleChipTrainer

    dev = jax.devices()[0]
    peak = cost.peak_flops_per_device(dev)  # raise on an unknown chip
    ici = comms.ici_bw_per_device(dev)
    if "v5 lite" in dev.device_kind.lower():
        check(peak == 197e12 and ici == 2.0e11,
              f"v5e peaks read {peak} FLOP/s, {ici} B/s")

    ds = _cnn_data(sz, CNN_STEPS, sz.cnn_batch, seed)
    # bf16 compute: what the CLI defaults to on TPU (cli._resolve_dtype).
    kw = dict(batch_size=sz.cnn_batch, eval_every=10,
              compute_dtype="bfloat16")
    x0 = np.asarray(ds.x_train[:sz.cnn_batch])
    y0 = one_hot(ds.y_train[:sz.cnn_batch])
    loss_of = jax.jit(lambda p: cnn.loss_fn(p, x0, y0, dropout_rng=None))

    out = {"peak_flops": peak, "ici_bw": ici}
    single = SingleChipTrainer(_cnn_cfg(sz, seed, **kw), ds)
    loss0 = float(loss_of(single.params))
    for tag, trainer in (
        ("single", single),
        ("sync_w1", SyncTrainer(_cnn_cfg(sz, seed, num_workers=1, **kw), ds)),
    ):
        res = trainer.train(log=_quiet)
        loss1 = float(loss_of(trainer.params))
        check(np.isfinite(loss0) and np.isfinite(loss1),
              f"{tag}: loss {loss0} -> {loss1}")
        check(loss1 < loss0,
              f"{tag}: loss did not fall in {CNN_STEPS} steps: "
              f"{loss0} -> {loss1}")
        check(0.0 <= res.final_accuracy <= 1.0 and len(res.history) == 3,
              f"{tag}: accuracy {res.final_accuracy}, evals {res.history}")
        check(all(leaf.devices() == {dev}
                  for leaf in jax.tree.leaves(trainer.params)),
              f"{tag}: params are not on {dev}")
        out[tag] = {"loss_step0": loss0, f"loss_step{CNN_STEPS}": loss1,
                    "eval_accuracy": res.final_accuracy,
                    "trainer_compile_s": round(res.compile_time_s, 3)}

    out["barrier"] = _time_barriers(single, ds, sz, peak)

    # Same device, same shapes: the test's own tolerance holds under both
    # policies (the two programs came out bit-equal on the chip, PR 21).
    diffs = {"fp32": _cnn_pair(sz, seed, ds, precision="fp32"),
             "bf16": _cnn_pair(sz, seed, ds, compute_dtype="bfloat16")}
    for policy, d in diffs.items():
        check(d < SYNC_ATOL,
              f"single vs sync W=1 ({policy}) differ by {d} >= {SYNC_ATOL}")
    out["single_vs_sync_w1_max_abs_diff"] = diffs
    return out


# -- phase 2: the LM trainer, einsum softmax and the flash kernel ------------


def _lm_spec(sz: Sizes):
    from ddl_tpu.models.transformer import LMSpec

    return LMSpec(**sz.lm)


def _lm_first_step(trainer, ds, batch: int):
    """First-step loss through the trainer's own one-step span program,
    and that compiled program (the trainer's state is left untouched)."""
    import jax
    import jax.numpy as jnp

    xs, ys, ws = (trainer.stage_batches(a, 1, batch)
                  for a in (ds.tokens, ds.targets, ds.weights))
    params = jax.tree.map(jnp.copy, trainer.params)
    opt = jax.tree.map(jnp.copy, trainer.opt_state)
    lowered = trainer.span_program(1).lower(
        params, opt, xs, ys, ws, jnp.int32(0))
    step = lowered.compile()
    loss = float(step(params, opt, xs, ys, ws, jnp.int32(0))[2])
    return loss, lowered, step, xs


def phase_lm_train(sz: Sizes, seed: int) -> dict:
    import jax
    import numpy as np

    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer

    spec = _lm_spec(sz)
    on_tpu = jax.devices()[0].platform == "tpu"
    ds = synthesize_copy(num_train=LM_STEPS * sz.lm_batch,
                         num_test=sz.lm_batch, seq_len=sz.lm_seq,
                         vocab=spec.vocab, seed=seed)
    out = {}
    for impl in ("xla", "flash"):
        cfg = SeqConfig(epochs=1, batch_size=sz.lm_batch, eval_every=0,
                        seed=seed, num_workers=1, scheme="full",
                        compute_dtype="bfloat16", attn_impl=impl, spec=spec)
        trainer = SeqTrainer(cfg, ds)
        first, _, step, _ = _lm_first_step(trainer, ds, sz.lm_batch)
        kernel = "tpu_custom_call" in step.as_text()
        if on_tpu:
            check(kernel == (impl == "flash"),
                  f"attn_impl={impl}: tpu_custom_call in program: {kernel}")
        res = trainer.train(log=_quiet)
        check(np.isfinite(first) and np.isfinite(res.final_loss),
              f"attn_impl={impl}: loss {first} -> {res.final_loss}")
        out[impl] = {"first_step_loss": first,
                     f"loss_step{LM_STEPS}": res.final_loss,
                     "kernel_in_program": kernel,
                     "trainer_compile_s": round(res.compile_time_s, 3)}
    a, b = out["xla"]["first_step_loss"], out["flash"]["first_step_loss"]
    check(abs(a - b) <= FLASH_RTOL * abs(a),
          f"first-step loss: xla {a} vs flash {b}")
    out["ring_bf16_grad_distance"] = _ring_grad_distance(sz, seed)
    return out


def _ring_grad_distance(sz: Sizes, seed: int) -> float:
    """The causal ring's gradients against the einsum oracle's, bf16, on
    one device — the only place the default run crosses the ring's
    ``lax.cond``, whose backward came out all-NaN on the chip until the
    score tile was accumulated in fp32 (PR 21; ``--chips 4`` runs the
    ring across chips)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.parallel import ring
    from ddl_tpu.parallel.mesh import make_mesh

    heads = sz.lm["num_heads"]
    shape = (2, sz.lm_seq // 4, heads, sz.lm["d_model"] // heads)
    q, k, v = (jax.random.normal(key, shape, jnp.float32)
               .astype(jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(seed), 3))
    ring_fn = ring.make_ring_attention(make_mesh(1), causal=True)

    def grads(attend):
        loss = lambda q, k, v: (attend(q, k, v).astype(jnp.float32) ** 2).sum()
        return [np.asarray(g, np.float32) for g in
                jax.grad(loss, argnums=(0, 1, 2))(q, k, v)]

    got = grads(ring_fn)
    want = grads(lambda q, k, v: ring.full_attention(q, k, v, causal=True))
    check(all(np.all(np.isfinite(g)) for g in got),
          "ring attention (bf16): non-finite gradients: "
          f"{[int((~np.isfinite(g)).sum()) for g in got]} of dq, dk, dv")
    dist = _relative_l2(got, want)
    check(dist <= RING_GRAD_RTOL,
          f"ring attention (bf16) gradients are {dist} away from the "
          f"einsum path's (> {RING_GRAD_RTOL})")
    return dist


# -- phase 3: the paged server -----------------------------------------------


def _serve(sz: Sizes, seed: int, prompts, **cfg_kw):
    from ddl_tpu.serve import InferenceEngine, Request, Scheduler, ServeConfig

    engine = InferenceEngine(ServeConfig(
        spec=_lm_spec(sz), slots=SERVE_SLOTS, capacity=sz.capacity,
        seed=seed, **cfg_kw))
    scheduler = Scheduler(engine)
    requests = [Request(id=i, prompt=p, max_new_tokens=sz.new_tokens)
                for i, p in enumerate(prompts)]
    scheduler.warmup(requests)  # compiles outside the run, as the CLI does
    done, _stats = scheduler.run(requests)
    programs = {"prefill": len(engine._prefill_fns),
                "decode": len(engine._decode_paged_fns)
                + (engine._decode_fn is not None)}
    return engine, done, programs


def _first_split(done_a, done_b):
    for i in sorted(done_a):
        ta, tb = done_a[i].tokens, done_b[i].tokens
        for pos, (x, y) in enumerate(zip(ta, tb)):
            if x != y:
                return i, pos
        if len(ta) != len(tb):
            return i, min(len(ta), len(tb))
    return None


def phase_serve(sz: Sizes, seed: int) -> dict:
    import numpy as np

    from ddl_tpu.data.lm import synthesize_prompts

    prompts = synthesize_prompts(num=SERVE_PROMPTS, min_len=sz.prompt_min,
                                 max_len=sz.prompt_max, vocab=sz.lm["vocab"],
                                 seed=seed)
    out = {}

    def all_ok(tag, done):
        bad = {i: c.status for i, c in done.items() if c.status != "ok"}
        check(len(done) == SERVE_PROMPTS and not bad
              and all(len(c.tokens) == sz.new_tokens for c in done.values()),
              f"{tag}: {len(done)} completions, not ok: {bad}")

    # fp32: paged == slot-major, the repo's own pin (test_serve_paged.py).
    paged, done_p, progs = _serve(sz, seed, prompts, page_size=sz.page_size)
    flat, done_f, _ = _serve(sz, seed, prompts, page_size=0)
    all_ok("fp32 paged", done_p)
    all_ok("fp32 slot-major", done_f)
    split = _first_split(done_p, done_f)
    out["fp32_paged"] = {"programs": progs,
                         "tokens_equal_slot_major": split is None}
    if split is not None:
        rid, pos = split
        prefix = np.concatenate(
            [prompts[rid], np.asarray(done_p[rid].tokens[:pos], np.int32)])
        rows = []
        for eng in (paged, flat):
            eng.reset()
            rows.append(eng.prefill(prefix, slot=0, request_id=rid,
                                    want_logits=True)[1][-1])
        gap = float(np.max(np.abs(rows[0] - rows[1])))
        out["fp32_paged"]["first_split"] = {
            "request": rid, "position": pos,
            "tokens": [done_p[rid].tokens[pos], done_f[rid].tokens[pos]],
            "logits_max_abs_diff": gap}
        check(gap <= SERVE_LOGIT_ATOL,
              f"paged vs slot-major: request {rid} splits at token {pos} and "
              f"that step's logits differ by {gap} > {SERVE_LOGIT_ATOL}")
    for tag, kw in (("bf16_paged", {}), ("bf16_paged_int8",
                                         {"kv_dtype": "int8"})):
        _, done, progs = _serve(sz, seed, prompts, page_size=sz.page_size,
                                compute_dtype="bfloat16", **kw)
        all_ok(tag, done)
        out[tag] = {"programs": progs, "completed_ok": len(done)}
    return out


# -- phase 4: the fused Adam kernel ------------------------------------------


def phase_fused_adam(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.models import cnn
    from ddl_tpu.parallel.layout import assign_layout
    from ddl_tpu.strategies.sync import ShardedAdam, _adam_flat

    specs = cnn.make_param_specs(conv_channels=sz.conv_channels,
                                 fc_sizes=sz.fc_sizes)
    sizes = {n: int(np.prod(s)) if s else 1 for n, s in specs}
    total = sum(sizes.values())
    shard = assign_layout("flat", 4, list(sizes), sizes).max_shard
    check(shard % 128 == 0, f"flat shard {shard} is not lane-aligned")
    # Compiled on the chip — the product path; interpreted only where a
    # rehearsal runs on the CPU (mesh.pallas_interpret_for's rule).
    interpret = jax.devices()[0].platform != "tpu"
    lr = 1e-4
    out = {}
    for n in (total, shard):  # the pad path, then the zero-copy reshape
        keys = jax.random.split(jax.random.PRNGKey(seed + n), 4)
        p, m, g = (jax.random.normal(k, (n,), jnp.float32) for k in keys[:3])
        v = jnp.abs(jax.random.normal(keys[3], (n,), jnp.float32))
        state = ShardedAdam(step=jnp.int32(0), m=m, v=v)
        ref = jax.jit(lambda p, s, g: _adam_flat(p, s, g, lr=lr))
        fused = jax.jit(lambda p, s, g: _adam_flat(
            p, s, g, lr=lr, fused=True, pallas_interpret=interpret))
        if not interpret:
            check("tpu_custom_call" in fused.lower(p, state, g)
                  .compile().as_text(), "fused Adam: no kernel in the program")
        (p_r, s_r), (p_f, s_f) = ref(p, state, g), fused(p, state, g)
        diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                   ((p_r, p_f), (s_r.m, s_f.m), (s_r.v, s_f.v)))
        check(p_f.shape == (n,) and np.isfinite(diff) and diff <= ADAM_ATOL,
              f"fused Adam n={n}: max abs diff {diff} > {ADAM_ATOL}")
        out[str(n)] = {"max_abs_diff": diff, "interpret": interpret}
    return out


# -- phase 5 (--chips 4): the paper's comparison across chips ----------------


def phase_cnn_4chip(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddl_tpu.data import one_hot
    from ddl_tpu.models import cnn
    from ddl_tpu.strategies.async_ps import AsyncTrainer
    from ddl_tpu.strategies.sync import SyncTrainer

    W = 4
    batch = W * sz.cnn_batch
    ds = _cnn_data(sz, PAIR_STEPS, batch, seed)
    devices = jax.devices()[:W]
    kw = dict(batch_size=batch, keep_prob=1.0, eval_every=0,
              precision="fp32")
    x0 = np.asarray(ds.x_train[:batch])
    y0 = one_hot(ds.y_train[:batch])
    loss_of = jax.jit(lambda p: cnn.loss_fn(p, x0, y0, dropout_rng=None))
    out = {}
    init = {}

    def run(tag, **cfg_kw):
        trainer = SyncTrainer(_cnn_cfg(sz, seed, **kw, **cfg_kw), ds)
        init.update({k: np.asarray(v) for k, v in trainer.params.items()})
        xs, ys = trainer._stage_epoch(PAIR_STEPS)
        lowered = trainer._chunk_fn(PAIR_STEPS).lower(
            trainer.params, trainer.opt_state, xs, ys, jnp.int32(0),
            jnp.int32(0), trainer.dropout_key)
        ops = _collectives(lowered.compile())
        res = trainer.train(log=_quiet)
        check(all(np.all(np.isfinite(v)) for v in res.params.values()),
              f"{tag}: non-finite params")
        out[tag] = {"collectives": _kinds(ops),
                    "batch_devices": _distinct_devices(xs),
                    "param_devices": len(
                        jax.tree.leaves(trainer.params)[0].devices()),
                    f"loss_step{PAIR_STEPS}": float(loss_of(res.params))}
        return trainer, res, lowered, ops

    with jax.default_matmul_precision("highest"):  # see UPDATE_RTOL
        _, one, _, _ = run("sync_w1", num_workers=1)
        _, dp, _, _ = run("sync_w4", num_workers=W)
        zero, z1, z_lowered, z_ops = run("zero1_flat_w4", num_workers=W,
                                         num_ps=W, layout="flat")
        loss_init = float(loss_of(init))

    loss_key = f"loss_step{PAIR_STEPS}"
    loss_one = out["sync_w1"][loss_key]
    check(loss_one < loss_init,
          f"W=1: loss did not fall in {PAIR_STEPS} steps")
    for tag, res in (("sync_w4", dp), ("zero1_flat_w4", z1)):
        dist = _relative_l2(*([tree[k] for k in init]
                              for tree in (res.params, one.params, init)))
        out[tag].update(
            update_distance_vs_w1=dist,
            max_abs_diff_vs_w1=_max_abs_diff(res.params, one.params))
        check(dist <= UPDATE_RTOL,
              f"{tag}: update is {dist} of its own size away from W=1's "
              f"(> {UPDATE_RTOL})")
        check(abs(out[tag][loss_key] - loss_one) <= LOSS_RTOL * loss_one,
              f"{tag}: loss {out[tag][loss_key]} vs W=1 {loss_one}")
        check(out[tag]["batch_devices"] == W
              and out[tag]["param_devices"] == W,
              f"{tag}: placement {out[tag]}")
    check("all-reduce" in out["sync_w4"]["collectives"],
          f"sync W=4 program has no all-reduce: {out['sync_w4']}")
    out["zero1_flat_w4"]["grad_reduction"] = _sharded_update_form(
        "zero1_flat_w4", z_lowered, z_ops, zero.opt_state.m.shape[0])
    for name in ("m", "v"):
        moment = getattr(zero.opt_state, name)
        n = _distinct_devices(moment)
        check(len(moment.addressable_shards) == W and n == W
              and {s.device for s in moment.addressable_shards}
              == set(devices),
              f"ZeRO-1 Adam {name}: {len(moment.addressable_shards)} shards "
              f"on {n} devices")
        check(moment.addressable_shards[0].data.shape[0] * W
              == moment.shape[0], f"ZeRO-1 Adam {name} is not split {W} ways")
    out["zero1_flat_w4"]["adam_shard_devices"] = W

    # Async sharded PS: two rounds of W pushes of one batch each.
    ads = dataclasses.replace(ds, x_train=ds.x_train[:2 * W * sz.cnn_batch],
                              y_train=ds.y_train[:2 * W * sz.cnn_batch])
    atr = AsyncTrainer(_cnn_cfg(sz, seed, batch_size=sz.cnn_batch,
                                eval_every=0, precision="fp32",
                                num_workers=W, num_ps=W, layout="block"),
                       ads)
    ares = atr.train(log=_quiet)
    check(all(np.all(np.isfinite(v)) for v in ares.params.values())
          and 0.0 <= ares.final_accuracy <= 1.0,
          "async_sharding W=4: non-finite result")
    check(_distinct_devices(atr.state.ps) == W,
          "async_sharding W=4: PS state is not spread over the chips")
    out["async_sharding_w4"] = {"rounds": 2,
                                "eval_accuracy": ares.final_accuracy,
                                "ps_devices": _distinct_devices(atr.state.ps)}
    return out


# -- phase 6 (--chips 4): the LM on a 2x2 dp x sp mesh ------------------------


def phase_lm_4chip(sz: Sizes, seed: int) -> dict:
    import jax
    import numpy as np

    from ddl_tpu.data.lm import synthesize_copy
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer

    spec = _lm_spec(sz)
    ds = synthesize_copy(num_train=LM_STEPS_4CHIP * sz.lm_batch,
                         num_test=sz.lm_batch, seq_len=sz.lm_seq_4chip,
                         vocab=spec.vocab, seed=seed)
    base = dict(epochs=1, batch_size=sz.lm_batch, eval_every=0, seed=seed,
                spec=spec)
    ring_kw = dict(num_workers=2, data_parallel=2, scheme="ring", zero1=True)

    # ring == full (tests/test_lm.py), fp32, first-step loss.
    full = SeqTrainer(SeqConfig(num_workers=1, scheme="full",
                                precision="fp32", **base), ds)
    loss_full, _, _, _ = _lm_first_step(full, ds, sz.lm_batch)
    ring32 = SeqTrainer(SeqConfig(precision="fp32", **ring_kw, **base), ds)
    loss_ring, lowered, program, xs = _lm_first_step(ring32, ds, sz.lm_batch)
    check(abs(loss_ring - loss_full) <= RING_RTOL * abs(loss_full),
          f"first-step loss: 2x2 ring+zero1 {loss_ring} vs one-device full "
          f"{loss_full}")
    ops = _collectives(program)
    check("collective-permute" in _kinds(ops),
          f"ring program has no collective-permute: {_kinds(ops)}")
    grad_form = _sharded_update_form("lm ring+zero1", lowered, ops,
                                     ring32.opt_state.m.shape[0])
    check(_distinct_devices(xs) == 4, "staged LM batch is not on 4 devices")

    # Then the run itself, bf16 compute as the CLI defaults on TPU.
    ring = SeqTrainer(SeqConfig(compute_dtype="bfloat16", **ring_kw, **base),
                      ds)
    res = ring.train(log=_quiet)
    check(np.isfinite(res.final_loss) and res.final_loss < loss_ring,
          f"ring loss {loss_ring} -> {res.final_loss} in {LM_STEPS_4CHIP} "
          "steps")
    check(all(np.all(np.isfinite(leaf))
              for leaf in jax.tree.leaves(res.params)),
          "ring: non-finite params after training")
    for name in ("m", "v"):
        moment = getattr(ring.opt_state, name)
        check(_distinct_devices(moment) == 4
              and moment.addressable_shards[0].data.shape[0] * 4
              == moment.shape[0],
              f"ring+zero1 Adam {name} is not split over 4 devices")
    check(len(jax.tree.leaves(ring.params)[0].devices()) == 4,
          "ring params are not on 4 devices")
    return {"first_step_loss": {"full_1dev_fp32": loss_full,
                                "ring_2x2_zero1_fp32": loss_ring},
            "collectives": _kinds(ops), "grad_reduction": grad_form,
            f"loss_step{LM_STEPS_4CHIP}_bf16": res.final_loss,
            "batch_devices": 4, "adam_shard_devices": 4}


ONE_CHIP = (("cnn_train", phase_cnn_train), ("lm_train", phase_lm_train),
            ("serve", phase_serve), ("fused_adam", phase_fused_adam))
FOUR_CHIPS = (("cnn_4chip", phase_cnn_4chip), ("lm_4chip", phase_lm_4chip))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phases and what they "
                         "are compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, for a control-flow run on the CPU; "
                         "never prints the result line")
    args = ap.parse_args(argv)

    from ddl_tpu.parallel.mesh import device_record
    from ddl_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {count}", file=sys.stderr)
        return 2
    print(json.dumps({"phase": "setup", "jax": jax.__version__,
                      **device_record(), "rehearse": args.rehearse,
                      "compile_cache": cache_dir}), flush=True)

    meter = CompileMeter()
    sizes = TINY if args.rehearse else FULL
    for name, fn in (FOUR_CHIPS if args.chips == 4 else ONE_CHIP):
        run_phase(name, fn, meter, sizes, args.seed)

    if args.rehearse:
        print("chip_smoke: rehearsal finished; this is not a chip run "
              f"(platform {dev.platform!r}, tiny sizes)", file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": device_record()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
