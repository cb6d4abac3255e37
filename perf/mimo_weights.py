"""Sizes and weights of the window/global routed-expert family
(``perf/configs/mimo-v2-flash-ep16.json``), made from ``--seed``.

The weights are the benchmark's own: made in fp32 leaf by leaf on the
device, rounded to bf16 once, and handed in that form both to the program
(which keeps them as they come) and, made again after the window, to the
plain reference (which upcasts one layer at a time). The tree is the
program's: ``embed``, ``blocks`` (a list of dicts a layer: ``ln1``,
``ln2``, ``wq``, ``wk``, ``wv``, ``wo``, ``sink`` in window layers, then
``wg``/``wu``/``wd`` in a dense layer or ``wr``/``rc``/``eg``/``eu``/
``ed`` in a routed one), ``lnf``, ``head``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

from . import weights as wts

HERE = os.path.dirname(os.path.abspath(__file__))
GLOBAL, WINDOW = 0, 1  # hybrid_layer_pattern
DENSE, MOE = 0, 1      # moe_layer_freq


@dataclasses.dataclass(frozen=True)
class MimoSizes:
    """The configuration as it is run, under the reference's own names."""

    name: str
    vocab: int
    d_model: int
    num_heads: int
    head_dim: int
    v_head_dim: int
    kv_heads: tuple[int, int]        # global, window
    rope_base: tuple[float, float]   # global, window
    rotary_dim: int
    window: int
    value_scale: float
    d_ff: int
    expert_ff: int
    router_width: int                # the published number of experts
    experts_held: tuple[int, int]    # first, one past the last
    top_k: int
    layer_kinds: tuple[int, ...]
    ffn_kinds: tuple[int, ...]
    eps: float
    reference: str = "mimo_reference"

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def load_sizes(name: str) -> MimoSizes:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        c = json.load(f)
    rotary = int(c["head_dim"] * c["partial_rotary_factor"]) // 2 * 2
    first = c["deployment"]["expert_rank"] * c["n_routed_experts"]
    if not len(c["hybrid_layer_pattern"]) == c["num_hidden_layers"] \
            == len(c["moe_layer_freq"]):
        raise ValueError(f"{name}: the layer patterns and num_hidden_layers "
                         "differ in length")
    return MimoSizes(
        name=name, vocab=c["vocab_size"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], head_dim=c["head_dim"],
        v_head_dim=c["v_head_dim"],
        kv_heads=(c["num_key_value_heads"], c["swa_num_key_value_heads"]),
        rope_base=(float(c["rope_theta"]), float(c["swa_rope_theta"])),
        rotary_dim=rotary, window=c["sliding_window"],
        value_scale=c["attention_value_scale"], d_ff=c["intermediate_size"],
        expert_ff=c["moe_intermediate_size"],
        router_width=c["deployment"]["router_width"],
        experts_held=(first, first + c["n_routed_experts"]),
        top_k=c["num_experts_per_tok"],
        layer_kinds=tuple(c["hybrid_layer_pattern"]),
        ffn_kinds=tuple(c["moe_layer_freq"]), eps=c["layernorm_epsilon"],
        reference=c.get("reference", "mimo_reference"))


def block_shapes(s: MimoSizes, layer: int) -> dict:
    e, hq = s.d_model, s.num_heads
    hkv = s.kv_heads[s.layer_kinds[layer]]
    out = {"ln1": (e,), "ln2": (e,), "wq": (e, hq * s.head_dim),
           "wk": (e, hkv * s.head_dim), "wv": (e, hkv * s.v_head_dim),
           "wo": (hq * s.v_head_dim, e)}
    if s.layer_kinds[layer] == WINDOW:
        out["sink"] = (hq,)
    if s.ffn_kinds[layer] == DENSE:
        out.update(wg=(e, s.d_ff), wu=(e, s.d_ff), wd=(s.d_ff, e))
    else:
        out.update(wr=(e, s.router_width), rc=(s.router_width,),
                   eg=(s.held, e, s.expert_ff), eu=(s.held, e, s.expert_ff),
                   ed=(s.held, s.expert_ff, e))
    return out


def make_weights(seed: int, sizes: MimoSizes, dtype="bfloat16"):
    """The program's tree on the default device, every leaf made in fp32
    by a jitted call of its own and rounded to ``dtype`` there. The
    embedding is of unit variance and the router's correction small, so
    that routing is near uniform over the experts (the configuration's
    ``assumed.weights`` has the reading that showed why)."""
    import jax
    import jax.numpy as jnp

    def leaf(name: str, shape, key):
        if name in ("ln1", "ln2", "lnf"):
            x = jnp.ones(shape, jnp.float32)
        elif name in ("sink", "embed"):
            x = jax.random.normal(key, shape, jnp.float32)
        elif name == "rc":
            x = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
            x = jax.random.uniform(key, shape, jnp.float32, -limit, limit)
        return x.astype(dtype)

    make = jax.jit(leaf, static_argnums=(0, 1))
    root = jax.random.wrap_key_data(jnp.asarray(wts.seed_words(seed),
                                                jnp.uint32))
    keys = jax.random.split(root, sizes.num_layers + 2)
    blocks = []
    for i in range(sizes.num_layers):
        shapes = block_shapes(sizes, i)
        ks = jax.random.split(keys[i], len(shapes))
        blocks.append({n: make(n, s, k)
                       for k, (n, s) in zip(ks, shapes.items())})
    e, v = sizes.d_model, sizes.vocab
    return {"embed": make("embed", (v, e), keys[-2]), "blocks": blocks,
            "lnf": make("lnf", (e,), keys[-1]),
            "head": make("head", (e, v), keys[-1])}
