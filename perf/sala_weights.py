"""Sizes and weights of the two-mixer block (``perf/configs/
minicpm-sala-l8.json``: lightning linear-attention layers and block-sparse
attention layers, one dense gated-SiLU FFN), made from ``--seed``.

The weights are the benchmark's own, as ``perf/mimo_weights.py`` makes
its family's: fp32 leaf by leaf on the device, rounded to bf16 once,
handed in that form to the program and, made again after the window, to
the plain reference. The tree is the program's: ``embed``, ``blocks`` (a
list of dicts a layer: ``ln1``, ``ln2``, ``wq``, ``wk``, ``wv``, ``qn``,
``kn``, ``wgate``, in a linear layer ``on``, ``wo``, then ``wg``, ``wu``,
``wd``), ``lnf``, ``head``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

from . import weights as wts

HERE = os.path.dirname(os.path.abspath(__file__))
SPARSE, LINEAR = "minicpm4", "lightning-attn"   # ``mixer_types``' names
GAINS = ("ln1", "ln2", "lnf", "qn", "kn", "on")


@dataclasses.dataclass(frozen=True)
class SalaSizes:
    """The configuration as it is run, under the reference's own names."""

    name: str
    vocab: int
    d_model: int
    num_heads: int
    head_dim: int
    kv_heads: int                 # of a sparse layer; a linear one: num_heads
    d_ff: int
    mixers: tuple[str, ...]
    eps: float
    rope_base: float
    scale_emb: float
    scale_depth: float
    depth: int                    # the PUBLISHED depth, in the cut too
    dim_model_base: int
    # the selector (the configuration's ``assumed.sparse_config``)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    reference: str = "sala_reference"

    @property
    def num_layers(self) -> int:
        return len(self.mixers)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth)

    @property
    def logit_scale(self) -> float:
        return self.dim_model_base / self.d_model


def load_sizes(name: str) -> SalaSizes:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        c = json.load(f)
    if (c["lightning_nh"], c["lightning_nkv"], c["lightning_head_dim"]) != (
            c["num_attention_heads"],) * 2 + (c["head_dim"],) \
            or len(c["mixer_types"]) != c["num_hidden_layers"] \
            or not (c["qk_norm"] and c["use_output_gate"]
                    and c["use_output_norm"] and c["attn_use_output_gate"]
                    and c["lightning_use_rope"]) or c["attn_use_rope"]:
        raise ValueError(f"{name}: not the two mixers this block writes")
    return SalaSizes(
        name=name, vocab=c["vocab_size"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], head_dim=c["head_dim"],
        kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        mixers=tuple(c["mixer_types"]), eps=c["rms_norm_eps"],
        rope_base=float(c["rope_theta"]), scale_emb=float(c["scale_emb"]),
        scale_depth=float(c["scale_depth"]),
        depth=c.get("published", c)["num_hidden_layers"],
        dim_model_base=c["dim_model_base"],
        reference=c.get("reference", "sala_reference"),
        **c["assumed"]["sparse_config"])


def block_shapes(s: SalaSizes, layer: int) -> dict:
    e, hd = s.d_model, s.num_heads * s.head_dim
    kv = (s.num_heads if s.mixers[layer] == LINEAR else s.kv_heads) \
        * s.head_dim
    out = {"ln1": (e,), "ln2": (e,), "wq": (e, hd), "wk": (e, kv),
           "wv": (e, kv), "qn": (s.head_dim,), "kn": (s.head_dim,),
           "wgate": (e, hd)}
    if s.mixers[layer] == LINEAR:
        out["on"] = (hd,)
    out.update(wo=(hd, e), wg=(e, s.d_ff), wu=(e, s.d_ff), wd=(s.d_ff, e))
    return out


def make_weights(seed: int, sizes: SalaSizes, dtype="bfloat16"):
    """The program's tree on the default device, every leaf made in fp32
    by a jitted call of its own and rounded to ``dtype`` there (the
    configuration's ``assumed.weights``)."""
    import jax
    import jax.numpy as jnp

    def leaf(name: str, shape, key):
        if name in GAINS:
            x = jnp.ones(shape, jnp.float32)
        elif name == "embed":
            x = jax.random.normal(key, shape, jnp.float32)
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
