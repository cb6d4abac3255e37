"""The benchmark's weights and token ids, made from ``--seed``.

The weights are the benchmark's own, not the program's: one jitted call
makes them on the device, the program is handed them, and after the
window the same call makes them again for the plain reference. The tree
is the layout of ``ddl_tpu.models.transformer.init_lm_params`` with the
blocks stacked on a leading layer axis; :func:`unstack` gives the
program's list of per-layer dicts.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One configuration's shapes, read from ``perf/configs/<name>.json``."""

    name: str
    vocab: int
    d_model: int
    num_heads: int
    num_layers: int
    d_ff: int
    rope_base: float = 10000.0
    reference: str = "reference"  # the module under perf/ that is its plain reference

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


def load_sizes(name: str) -> Sizes:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    return Sizes(
        name=name, vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        rope_base=float(cfg.get("rotary_emb_base", 10000)),
        reference=cfg.get("reference", "reference"),
    )


def seed_words(seed: int, stream: int = 0) -> np.ndarray:
    """Two uint32 words from a seed of any size (the driver's are above
    2**31) and a stream number: a threefry key, or a numpy seed."""
    return np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def builder(sizes: Sizes):
    """``words -> weights``: a pure function of a threefry key's two
    words, to be jitted alone or traced inside another program."""
    import jax
    import jax.numpy as jnp

    e, f, v, n = sizes.d_model, sizes.d_ff, sizes.vocab, sizes.num_layers
    shapes = {"wq": (e, e), "wk": (e, e), "wv": (e, e), "wo": (e, e),
              "w1": (e, f), "w2": (f, e)}

    def build(words):
        key = jax.random.wrap_key_data(words)

        def glorot(k, shape):
            limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
            return jax.random.uniform(k, shape, jnp.float32, -limit, limit)

        keys = jax.random.split(key, 2 + len(shapes))
        blocks = {name: glorot(keys[2 + i], (n,) + shape)
                  for i, (name, shape) in enumerate(shapes.items())}
        for name, width in (("ln1_g", e), ("ln2_g", e)):
            blocks[name] = jnp.ones((n, width), jnp.float32)
        for name, width in (("ln1_b", e), ("ln2_b", e), ("b1", f), ("b2", e)):
            blocks[name] = jnp.zeros((n, width), jnp.float32)
        return {"embed": glorot(keys[0], (v, e)), "blocks": blocks,
                "lnf_g": jnp.ones((e,), jnp.float32),
                "lnf_b": jnp.zeros((e,), jnp.float32),
                "head": glorot(keys[1], (e, v))}

    return build


def key_words(seed: int):
    import jax.numpy as jnp

    return jnp.asarray(seed_words(seed), jnp.uint32)


def make_weights(seed: int, sizes: Sizes, sharding=None):
    """fp32 weights on the device in one jitted call, blocks stacked."""
    import jax

    return jax.jit(builder(sizes), out_shardings=sharding)(key_words(seed))


def stack_host(tree) -> dict:
    """The program's tree (``blocks`` a list of dicts) as numpy arrays in
    the stacked form."""
    out = {k: np.asarray(v) for k, v in tree.items() if k != "blocks"}
    out["blocks"] = {k: np.stack([np.asarray(b[k]) for b in tree["blocks"]])
                     for k in tree["blocks"][0]}
    return out


def unstack(weights) -> dict:
    """The stacked tree as the program's: ``blocks`` a list of dicts."""
    n = next(iter(weights["blocks"].values())).shape[0]
    out = {k: v for k, v in weights.items() if k != "blocks"}
    out["blocks"] = [{k: v[i] for k, v in weights["blocks"].items()}
                     for i in range(n)]
    return out
