"""Sizes and weights of the latent-attention routed-expert block
(``perf/configs/kimi-k2-ep32.json``), made from ``--seed``.

The weights are the benchmark's own, as ``perf/mimo_weights.py`` makes
that family's: fp32 leaf by leaf on the device, rounded to bf16 once,
handed in that form to the program and, made again after the window, to
the plain reference. The tree is the program's: ``embed``, ``blocks`` (a
list of dicts a layer: ``ln1``, ``ln2``, ``wqa``, ``qn``, ``wqb``,
``wkva``, ``kvn``, ``wkvb``, ``wo``, then ``wg``/``wu``/``wd`` in a
dense layer or ``wr``/``rc``/``eg``/``eu``/``ed``/``sg``/``su``/``sd``
in a routed one), ``lnf``, ``head``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

from . import weights as wts

HERE = os.path.dirname(os.path.abspath(__file__))
DENSE, MOE = 0, 1
GAINS = ("ln1", "ln2", "lnf", "qn", "kvn")


@dataclasses.dataclass(frozen=True)
class K2Sizes:
    """The configuration as it is run, under the reference's own names."""

    name: str
    vocab: int
    d_model: int
    num_heads: int
    q_lora: int
    kv_lora: int
    nope_dim: int
    rope_dim: int
    v_head_dim: int
    rope_base: float
    rope_factor: float
    rope_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    d_ff: int
    expert_ff: int
    shared_ff: int
    router_width: int                # the published number of experts
    experts_held: tuple[int, int]    # first, one past the last
    top_k: int
    route_scale: float
    ffn_kinds: tuple[int, ...]
    eps: float
    reference: str = "k2_reference"

    @property
    def num_layers(self) -> int:
        return len(self.ffn_kinds)

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_row(self) -> int:
        return self.kv_lora + self.rope_dim


def load_sizes(name: str) -> K2Sizes:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        c = json.load(f)
    y, d = c["rope_scaling"], c["deployment"]
    if y["type"] != "yarn" or c["moe_layer_freq"] != 1:
        raise ValueError(f"{name}: not a YaRN config with every layer past "
                         "first_k_dense_replace routed")
    first = d["expert_rank"] * c["n_routed_experts"]
    dense = c["first_k_dense_replace"]
    return K2Sizes(
        name=name, vocab=c["vocab_size"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], q_lora=c["q_lora_rank"],
        kv_lora=c["kv_lora_rank"], nope_dim=c["qk_nope_head_dim"],
        rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_base=float(c["rope_theta"]), rope_factor=float(y["factor"]),
        rope_original=y["original_max_position_embeddings"],
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"]),
        d_ff=c["intermediate_size"], expert_ff=c["moe_intermediate_size"],
        shared_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        router_width=d["router_width"],
        experts_held=(first, first + c["n_routed_experts"]),
        top_k=c["num_experts_per_tok"],
        route_scale=c["routed_scaling_factor"],
        ffn_kinds=(DENSE,) * dense + (MOE,) * (c["num_hidden_layers"] - dense),
        eps=c["rms_norm_eps"], reference=c.get("reference", "k2_reference"))


def block_shapes(s: K2Sizes, layer: int) -> dict:
    e, h = s.d_model, s.num_heads
    out = {"ln1": (e,), "ln2": (e,), "wqa": (e, s.q_lora), "qn": (s.q_lora,),
           "wqb": (s.q_lora, h * (s.nope_dim + s.rope_dim)),
           "wkva": (e, s.latent_row), "kvn": (s.kv_lora,),
           "wkvb": (s.kv_lora, h * (s.nope_dim + s.v_head_dim)),
           "wo": (h * s.v_head_dim, e)}
    if s.ffn_kinds[layer] == DENSE:
        out.update(wg=(e, s.d_ff), wu=(e, s.d_ff), wd=(s.d_ff, e))
    else:
        f, sf = s.expert_ff, s.shared_ff
        out.update(wr=(e, s.router_width), rc=(s.router_width,),
                   eg=(s.held, e, f), eu=(s.held, e, f), ed=(s.held, f, e),
                   sg=(e, sf), su=(e, sf), sd=(sf, e))
    return out


def make_weights(seed: int, sizes: K2Sizes, dtype="bfloat16"):
    """The program's tree on the default device, every leaf made in fp32
    by a jitted call of its own and rounded to ``dtype`` there; the
    distributions of ``perf/mimo_weights.py`` (the configuration's
    ``assumed.weights``): a unit-variance embedding and a small router
    correction keep the routing near uniform over the experts."""
    import jax
    import jax.numpy as jnp

    def leaf(name: str, shape, key):
        if name in GAINS:
            x = jnp.ones(shape, jnp.float32)
        elif name == "embed":
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
