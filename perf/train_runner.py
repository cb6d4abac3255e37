"""The training runner: ``SeqTrainer.span_program`` at the timed sizes.

Set-up builds one object (the trainer's compiled span program with its
state), hands it the benchmark's weights, drives it through the first
``check.steps`` steps of the seed's batches and keeps what the
comparison needs (each step's loss, the first gradient's leaf norms out
of Adam's first moment, the parameters' change). The window goes on
from that same object and state. After the window the program's state
is freed and the plain reference follows the same steps.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib

import numpy as np

from . import compare, harness, weights as wts


def host_batches(traffic: dict, sizes, seed: int):
    """``[nb * B, T]`` tokens and next-token targets, every row scored.
    Ids are uniform over the vocabulary, every row different."""
    n = traffic["staged_batches"] * traffic["batch"]
    rng = wts.host_rng(seed, 1)
    ids = rng.integers(0, sizes.vocab, (n, traffic["seq_len"] + 1),
                       dtype=np.int32)
    return ids[:, :-1], ids[:, 1:], np.ones(ids[:, 1:].shape, np.float32)


@dataclasses.dataclass
class Session:
    cell: dict
    sizes: wts.Sizes
    trainer: object
    span: object
    params: object = None
    opt: object = None
    staged: tuple = ()
    firsts: list = dataclasses.field(default_factory=list)
    next_batch: int = 0
    seed: int = 0

    @property
    def tokens_per_call(self) -> int:
        t = self.cell["traffic_params"]
        return self.cell["span_steps"] * t["batch"] * t["seq_len"]


def build(cell: dict, sizes: wts.Sizes, seed: int) -> Session:
    """The trainer as the CLI builds it, on the cell's settings."""
    from ddl_tpu.data.lm import LMDataset
    from ddl_tpu.models.transformer import LMSpec
    from ddl_tpu.strategies.seq import SeqConfig, SeqTrainer

    traffic = cell["traffic_params"]
    spec = LMSpec(vocab=sizes.vocab, d_model=sizes.d_model,
                  num_heads=sizes.num_heads, num_layers=sizes.num_layers,
                  d_ff=sizes.d_ff, rope_base=sizes.rope_base)
    x, y, w = host_batches(traffic, sizes, seed)
    b = traffic["batch"]
    ds = LMDataset(tokens=x, targets=y, weights=w, test_tokens=x[:b],
                   test_targets=y[:b], test_weights=w[:b])
    cfg = SeqConfig(epochs=1, batch_size=b, eval_every=0, seed=0, spec=spec,
                    **cell["trainer"])
    trainer = SeqTrainer(cfg, ds)
    session = Session(cell=cell, sizes=sizes, trainer=trainer,
                      span=trainer.span_program(cell["span_steps"]))
    reset(session, seed, (x, y, w))
    return session


def reset(session: Session, seed: int, batches=None) -> None:
    """Fresh state from ``seed``: the benchmark's weights in the
    trainer's own placement, zero moments, the seed's batches staged."""
    import jax
    import jax.numpy as jnp

    tr = session.trainer
    traffic = session.cell["traffic_params"]
    if batches is None:
        batches = host_batches(traffic, session.sizes, seed)
    # The trainer's own initial state gives the placement; after that the
    # session holds the only references (the span program donates them).
    old_params = session.params if session.params is not None else tr.params
    old_opt = session.opt if session.opt is not None else tr.opt_state
    tr.params = tr.opt_state = session.params = session.opt = None
    shardings = jax.tree.map(lambda a: a.sharding, old_params)
    replicated = jax.tree.leaves(shardings)[0]
    del old_params
    session.opt = jax.tree.map(
        lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype), a.sharding),
        old_opt)
    del old_opt
    fresh = wts.unstack(wts.make_weights(seed, session.sizes, replicated))
    session.params = jax.tree.map(jax.device_put, fresh, shardings)
    del fresh
    nb, b = traffic["staged_batches"], traffic["batch"]
    session.staged = tuple(tr.stage_batches(a, nb, b) for a in batches)
    session.firsts = [jnp.int32(i) for i in range(nb)]
    session.next_batch = 0
    session.seed = seed


def call(session: Session):
    """One call of the span program on the next staged batches."""
    nb = session.cell["traffic_params"]["staged_batches"]
    first = session.firsts[session.next_batch % nb]
    session.params, session.opt, loss = session.span(
        session.params, session.opt, *session.staged, first)
    session.next_batch += session.cell["span_steps"]
    return loss


def _moment_leaves(session: Session):
    """Adam's first moment as a tree shaped like the parameters."""
    import jax

    m = session.opt.m
    if isinstance(m, (dict, list)):
        return m
    # ZeRO-1: one flat, padded vector in the parameters' leaf order.
    leaves, treedef = jax.tree.flatten(session.params)
    out, at = [], 0
    for leaf in leaves:
        out.append(m[at:at + leaf.size].reshape(leaf.shape))
        at += leaf.size
    return jax.tree.unflatten(treedef, out)


def _change_norms(session: Session) -> dict:
    """Leaf norms of (parameters now) - (parameters the seed gave)."""
    import jax
    import jax.numpy as jnp

    build = wts.builder(session.sizes)

    def fn(params, words):
        start = wts.unstack(build(words))
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), params, start)

    got = jax.jit(fn)(session.params, wts.key_words(session.seed))
    return compare.flatten_norms(jax.device_get(got))


def followed_steps(session: Session) -> dict:
    """Drive the session's own span program through the steps the
    reference follows, and read what the comparison needs."""
    import jax

    if session.cell["span_steps"] != 1:
        raise ValueError("the followed steps need span_steps 1")
    steps = session.cell["check"]["steps"]
    losses, gradient = [], None
    for i in range(steps):
        losses.append(float(call(session)))
        if i == 0:  # Adam's first moment after one step is (1 - b1) g
            gradient = jax.tree.map(
                lambda a: a / np.float32(1.0 - compare.ADAM_B1),
                wts.stack_host(jax.device_get(_moment_leaves(session))))
    return {"losses": losses, "gradient_tree": gradient,
            "change": _change_norms(session)}


def window(session: Session, seconds: float, depth: int = 1) -> dict:
    """Call the span program for ``seconds``. A call is dispatched before
    the one before it is waited for, so the device never waits for the
    host, and the window overruns ``seconds`` by at most ``depth`` calls."""
    inflight = collections.deque()
    done = 0
    t0 = harness.now()
    while True:
        inflight.append(call(session))
        if len(inflight) > depth:
            inflight.popleft().block_until_ready()
            done += 1
            if harness.now() - t0 >= seconds:
                break
    while inflight:
        last = inflight.popleft()
        last.block_until_ready()
        done += 1
    elapsed = harness.now() - t0
    return {"calls": done, "elapsed_s": elapsed,
            "tokens": done * session.tokens_per_call,
            "tokens_per_s": done * session.tokens_per_call / elapsed,
            "last_loss": float(last)}


def reference_readings(cell: dict, sizes: wts.Sizes, seed: int, *,
                       precision: str = "fp32", keep_rows: int | None = None,
                       devices=None) -> dict:
    """The plain reference through the same steps on the same batches.
    ``keep_rows`` (a fault for the tests and the limits) scores only the
    first rows of every batch."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"{__package__}.{sizes.reference}")

    traffic, check = cell["traffic_params"], cell["check"]
    x, y, w = host_batches(traffic, sizes, seed)
    if keep_rows is not None:
        w = w.copy().reshape(-1, traffic["batch"], w.shape[-1])
        w[:, keep_rows:] = 0.0
        w = w.reshape(-1, w.shape[-1])
    b = traffic["batch"]
    dev = (devices or jax.devices())[0]
    with jax.default_device(dev):
        weights = wts.make_weights(seed, sizes)
        start_fn = jax.jit(lambda new, words: ref.leaf_norms(jax.tree.map(
            jnp.subtract, new, wts.builder(sizes)(words))))
        m = jax.tree.map(jnp.zeros_like, weights)
        v = jax.tree.map(jnp.zeros_like, weights)
        step = jnp.int32(0)
        losses, gradient = [], None
        for i in range(check["steps"]):
            rows = slice(i * b, (i + 1) * b)
            weights, m, v, step, loss, grads = ref.train_step(
                weights, m, v, step, jnp.asarray(x[rows]),
                jnp.asarray(y[rows]), jnp.asarray(w[rows]), sizes=sizes,
                precision=precision, rows=check["rows"],
                lr=cell["trainer"].get("learning_rate", 1e-3))
            losses.append(float(loss))
            if i == 0:
                gradient = jax.device_get(grads)
            del grads
        change = compare.flatten_norms(jax.device_get(
            start_fn(weights, wts.key_words(seed))))
    del weights, m, v
    return {"losses": losses, "gradient_tree": gradient, "change": change}


def free(session: Session) -> None:
    session.params = session.opt = None
    session.staged = ()
    session.trainer = session.span = None
    gc.collect()


def run(cell: dict, sizes: wts.Sizes, args, devices, t_start: float,
        compiles: harness.CompileCounter) -> dict:
    from . import readers

    session = build(cell, sizes, args.seed)
    built_s = harness.now() - t_start
    program = followed_steps(session)
    compiled_before = compiles.count
    setup_s = harness.now() - t_start
    trace_path = None
    if args.trace:
        with harness.profiler_trace(cell["name"]) as found:
            facts = window(session, min(args.seconds, cell["trace_seconds"]))
        trace_path = found["xplane"]
    else:
        facts = window(session, args.seconds)
    compiled_inside = compiles.count - compiled_before
    peak = harness.memory_peak_bytes(devices)
    free(session)

    reference = reference_readings(cell, sizes, args.seed, devices=devices)
    numbers = compare.train_numbers(program, reference)
    numbers["compiles_in_window"] = compiled_inside
    checked = compare.checked_from(numbers, cell["check"]["limits"])
    end_to_end = {"setup_s": setup_s,
                  "train_tokens_per_s": facts["tokens_per_s"]}
    per_layer, device_extra, breakdown = {}, {}, None
    if args.trace and trace_path:
        context = {"cell": cell, "sizes": sizes, "facts": facts,
                   "devices": devices, "trace_path": trace_path,
                   "rehearse": args.rehearse}
        per_layer, device_extra, breakdown = readers.read_all(context)
    return {"checked": checked, "attempted": facts["calls"],
            "failed": 0, "end_to_end": end_to_end, "per_layer": per_layer,
            "device_extra": device_extra, "breakdown": breakdown,
            "memory_peak_bytes": peak,
            "info": {"numbers": numbers, "window": facts,
                     "setup_compiles": compiled_before,
                     "setup_compile_s": compiles.seconds,
                     "setup_built_s": built_s}}
