"""The second decoder family: global, window, latent, linear and
block-sparse attention layers mixed by a per-layer pattern, routed
experts (beside a shared one where the spec has it), written once over
a cache view.

Where ``models.transformer`` writes its dense block three times (full,
contiguous cache, paged cache), this family has ONE block function,
:func:`apply_block`, over a (mixer kind, FFN kind) pair a layer. The
block projects, rotates and normalises by the layer's kind
(:func:`project`); what it attends over is the caller's: ``mix(layer,
q, k, v) -> a`` is the cache view. The uncached forward
(:func:`apply_hybrid`, the tests' oracle) passes plain attention over
the call's own rows; the serving engine passes :class:`PagedMixer`,
which writes the rows into the page pools and attends what they hold.

The block (every symbol a field of :class:`HybridSpec`): ``h = x +
Attn(RMS(x))``, ``y = h + FFN(RMS(h))``, no bias anywhere, a final RMS
and an untied head with fp32 logits.

- **Attention.** ``num_heads`` query heads of ``head_dim``; K heads of
  ``head_dim`` and V heads of ``v_head_dim``, ``kv_heads_global`` or
  ``kv_heads_window`` of them by the layer's kind; V is scaled by
  ``value_scale``; rotary on the first ``rotary_dim`` dimensions of each
  Q and K head (pairs ``(i, i + rotary_dim / 2)``) with a base per kind.
  A global layer attends every earlier position, a window layer the
  last ``window`` (its own included) and adds a learned sink logit a
  head to the softmax's denominator.
- **Latent attention** (kind ``LATENT``; every earlier position, as a
  global layer). ``c_q = RMS(x W_qa)`` (``q_lora_rank``, its own gain),
  ``q = c_q W_qb`` -> ``num_heads`` heads ``[q_n (nope_dim) | q_r
  (rope_dim)]``; ``[c | k_r] = x W_kva`` (``kv_lora_rank | rope_dim``),
  ``c = RMS(c)`` (its own gain), ``k_r`` ONE a token, shared by all
  heads; rotary on ``q_r`` and ``k_r`` (:func:`yarn_freqs`, pairs ``(i,
  i + rope_dim / 2)``); ``[k_n,h | v_h] = c W_kvb`` a head (``nope_dim |
  v_head_dim``); scores ``latent_scale x (q_n . k_n + q_r . k_r)``.
  What a cache keeps of a token is ``[c | k_r]`` alone, after the norm
  and the rotation: the cache view is handed that row in ``k``'s place
  and up-projects it (the published form), or folds ``W_kvb`` into the
  query and the output instead (the absorbed form: ``num_heads`` query
  heads of ``kv_lora_rank + rope_dim`` over ONE K/V head whose V is the
  first ``kv_lora_rank`` values of K). Both are the same attention.
- **Linear attention** (kind ``LINEAR``; ``ops.linear_attention``).
  ``num_heads`` heads of ``head_dim``, as many K/V heads: ``q = RMS(x
  W_q)``, ``k = RMS(x W_k)`` a head (gains ``qn``, ``kn`` of
  ``head_dim``, shared by the heads), ``v = x W_v``; rotary on the whole
  head of q and k (base ``rope_base_global``, pairs ``(i, i + head_dim
  / 2)``); ``q`` times ``head_dim ** -0.5``. A head keeps a state ``S
  [head_dim, v_head_dim]`` in fp32, ``S_t = lambda S_(t-1) + k_t^T
  v_t``, ``o_t = q_t S_t``, and nothing else of a token. The mixer's
  output is ``(sigmoid(x W_gate) * RMS(concat o)) W_o``: a norm over the
  concatenated heads (gain ``on``) and a gate a value.
- **Block-sparse attention** (kind ``SPARSE``; ``ops.sparse_attention``,
  the sizes ``HybridSpec.selector``). ``kv_heads_global`` K/V heads, no
  rotary, the same q/k norms, the output ``(sigmoid(x W_gate) * a)
  W_o``. ``a`` is causal softmax attention over every earlier row up to
  ``sparse_dense_len`` rows of context, and past it over the
  ``sparse_topk`` blocks a selector without parameters picks for the
  query from means of the K rows. It keeps K and V rows in one pool of
  the global page group and the selector's means beside them.
- **Scalings** (1 where a spec gives none): the embedding times
  ``embed_scale``, each residual branch times ``residual_scale``, the
  head's input times ``logit_scale``.
- **FFN.** Dense gated SiLU of width ``d_ff``, or ``num_experts`` routed
  experts of width ``expert_ff``, ``experts_per_token`` a token
  (``ops.moe``), their weights times ``route_scale`` where the spec
  gives one. The layer holds the experts ``experts_held[0] ..
  experts_held[1] - 1`` and computes their part alone; a shared expert
  of width ``shared_ff`` (0: none) sees every token, whole, beside them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import (kv_cache, linear_attention, moe, paged_attention,
                   sparse_attention)

GLOBAL, WINDOW = 0, 1   # layer_kinds, as the published pattern counts them
LATENT = 2              # a compressed row a token in the global page group
LINEAR = 3              # no rows: a state a slot, [heads, head_dim, v_head_dim]
SPARSE = 4              # rows in the global group, a selector's means beside
GATED = (LINEAR, SPARSE)  # q/k norms a head, an output gate
DENSE, MOE = 0, 1       # ffn_kinds


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """Shapes and the per-layer pattern. The defaults are the tests'
    toy; :data:`NAMED_SPECS` has the published widths."""

    vocab: int = 64
    d_model: int = 32
    num_heads: int = 4
    head_dim: int = 12
    v_head_dim: int = 8
    kv_heads_global: int = 1
    kv_heads_window: int = 2
    window: int = 8
    rope_base_global: float = 5_000_000.0
    rope_base_window: float = 10_000.0
    rotary_dim: int = 4
    value_scale: float = 0.707
    d_ff: int = 64
    expert_ff: int = 16
    num_experts: int = 16
    experts_per_token: int = 4
    experts_held: tuple[int, int] = (0, 16)   # first, one past the last
    layer_kinds: tuple[int, ...] = (GLOBAL, WINDOW, WINDOW, GLOBAL)
    ffn_kinds: tuple[int, ...] = (DENSE, MOE, MOE, MOE)
    norm_eps: float = 1e-5
    # Routed layers: a factor on the routed weights, a shared expert.
    route_scale: float | None = None
    shared_ff: int = 0
    # Latent layers (none of it read by the other kinds). Their rotary
    # base is ``rope_base_global``, stretched as YaRN stretches it.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    rope_factor: float = 1.0
    rope_original: int = 4096     # positions before the stretch
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # Scalings of the residual stream (1: none).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # Sparse layers' selector (``ops.sparse_attention.Selector``): a
    # block is the pool's page, a compressed key the mean of two
    # neighbouring groups of ``sparse_stride`` rows.
    sparse_block: int = 64
    sparse_stride: int = 16
    sparse_topk: int = 64
    sparse_init: int = 1
    sparse_local: int = 32
    sparse_dense_len: int = 8192

    def __post_init__(self):
        if len(self.layer_kinds) != len(self.ffn_kinds):
            raise ValueError("layer_kinds and ffn_kinds differ in length")
        first, end = self.experts_held
        if not 0 <= first < end <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"[0, {self.num_experts}]")
        for name in ("kv_heads_global", "kv_heads_window"):
            if self.num_heads % getattr(self, name):
                raise ValueError(f"num_heads ({self.num_heads}) must be a "
                                 f"multiple of {name}")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary_dim ({self.rotary_dim}) must be even "
                             f"and at most head_dim ({self.head_dim})")
        w = self.window
        if WINDOW in self.layer_kinds and (w < 8 or w & (w - 1)):
            raise ValueError(f"window ({w}) must be a power of two >= 8: "
                             "a window layer's prefill works on blocks of "
                             "one window")
        if LATENT in self.layer_kinds:
            for name in ("q_lora_rank", "kv_lora_rank", "nope_dim",
                         "rope_dim"):
                if getattr(self, name) < 1:
                    raise ValueError(f"a latent layer needs {name} >= 1")
            if self.rope_dim % 2:
                raise ValueError(f"rope_dim ({self.rope_dim}) must be even")
        if LINEAR in self.layer_kinds and self.head_dim % 2:
            raise ValueError(f"a linear layer rotates the whole head: "
                             f"head_dim ({self.head_dim}) must be even")
        if SPARSE in self.layer_kinds:
            sel = self.selector
            if self.head_dim != self.v_head_dim:
                raise ValueError("a sparse layer keeps K and V rows in one "
                                 "pool: head_dim must equal v_head_dim")
            if sel.block % sel.stride or sel.init + sel.local > sel.topk \
                    or sel.dense_len < sel.topk * sel.block:
                raise ValueError(
                    f"{sel}: a block must be whole groups, the blocks "
                    "always taken within topk, and dense_len at least "
                    "topk blocks (past it a query has topk to choose)")

    @property
    def selector(self) -> sparse_attention.Selector:
        return sparse_attention.Selector(
            self.sparse_block, self.sparse_stride, self.sparse_topk,
            self.sparse_init, self.sparse_local, self.sparse_dense_len)

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def kv_heads(self, layer: int) -> int:
        kind = self.layer_kinds[layer]
        return {WINDOW: self.kv_heads_window,
                LINEAR: self.num_heads}.get(kind, self.kv_heads_global)

    @property
    def latent_row(self) -> int:
        """Values a latent layer caches a token: ``[c | k_r]``."""
        return self.kv_lora_rank + self.rope_dim

    @property
    def latent_scale(self) -> float:
        """A latent layer's score scale: ``(nope_dim + rope_dim) ** -0.5``
        times the square of YaRN's ``0.1 x mscale_all_dim x ln(factor) +
        1`` (1 without a stretch)."""
        m = 1.0
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def layers_of(self, kind: int) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    def block_shapes(self, layer: int) -> dict[str, tuple[int, ...]]:
        """Every leaf of one layer's weights, by name."""
        e, hq, hkv = self.d_model, self.num_heads, self.kv_heads(layer)
        out = {"ln1": (e,), "ln2": (e,)}
        if self.layer_kinds[layer] == LATENT:
            ql, kl = self.q_lora_rank, self.kv_lora_rank
            out.update(wqa=(e, ql), qn=(ql,),
                       wqb=(ql, hq * (self.nope_dim + self.rope_dim)),
                       wkva=(e, self.latent_row), kvn=(kl,),
                       wkvb=(kl, hq * (self.nope_dim + self.v_head_dim)))
        else:
            out.update(wq=(e, hq * self.head_dim),
                       wk=(e, hkv * self.head_dim),
                       wv=(e, hkv * self.v_head_dim))
        if self.layer_kinds[layer] in GATED:
            out.update(qn=(self.head_dim,), kn=(self.head_dim,),
                       wgate=(e, hq * self.v_head_dim))
        if self.layer_kinds[layer] == LINEAR:
            out["on"] = (hq * self.v_head_dim,)
        out["wo"] = (hq * self.v_head_dim, e)
        if self.layer_kinds[layer] == WINDOW:
            out["sink"] = (hq,)
        if self.ffn_kinds[layer] == DENSE:
            out.update(wg=(e, self.d_ff), wu=(e, self.d_ff),
                       wd=(self.d_ff, e))
        else:
            f, h = self.expert_ff, self.held
            out.update(wr=(e, self.num_experts), rc=(self.num_experts,),
                       eg=(h, e, f), eu=(h, e, f), ed=(h, f, e))
            if self.shared_ff:
                sf = self.shared_ff
                out.update(sg=(e, sf), su=(e, sf), sd=(sf, e))
        return out

    @property
    def num_params(self) -> int:
        blocks = sum(math.prod(s) for i in range(self.num_layers)
                     for s in self.block_shapes(i).values())
        return 2 * self.vocab * self.d_model + self.d_model + blocks


# The published widths, cut to what one chip of a stated deployment
# holds (perf/configs/ has the deployment and every assumption).
NAMED_SPECS = {
    "mimo-v2-flash-ep16": HybridSpec(
        vocab=19072, d_model=4096, num_heads=64, head_dim=192,
        v_head_dim=128, kv_heads_global=4, kv_heads_window=8, window=128,
        rope_base_global=5_000_000.0, rope_base_window=10_000.0,
        rotary_dim=64, value_scale=0.707, d_ff=16384, expert_ff=2048,
        num_experts=256, experts_per_token=8, experts_held=(0, 16),
        layer_kinds=(GLOBAL, WINDOW, WINDOW, WINDOW, WINDOW, WINDOW, GLOBAL),
        ffn_kinds=(DENSE, MOE, MOE, MOE, MOE, MOE, MOE)),
    "kimi-k2-ep32": HybridSpec(
        vocab=20480, d_model=7168, num_heads=64, head_dim=192,
        v_head_dim=128, q_lora_rank=1536, kv_lora_rank=512, nope_dim=128,
        rope_dim=64, rope_base_global=50_000.0, rope_factor=32.0,
        rope_original=4096, rope_beta_fast=1.0, rope_beta_slow=1.0,
        rope_mscale_all_dim=1.0, d_ff=18432,
        expert_ff=2048, shared_ff=2048, num_experts=384,
        experts_per_token=8, experts_held=(0, 12), route_scale=2.827,
        layer_kinds=(LATENT,) * 5, ffn_kinds=(DENSE, MOE, MOE, MOE, MOE),
        norm_eps=1e-6),
    # Published layers 9-16 of 32; the residual scale keeps the 32.
    "minicpm-sala-l8": HybridSpec(
        vocab=73448, d_model=4096, num_heads=32, head_dim=128,
        v_head_dim=128, kv_heads_global=2, rope_base_global=10_000.0,
        d_ff=16384, layer_kinds=(SPARSE,) + (LINEAR,) * 6 + (SPARSE,),
        ffn_kinds=(DENSE,) * 8, norm_eps=1e-6, embed_scale=12.0,
        residual_scale=1.4 / math.sqrt(32), logit_scale=256 / 4096,
        sparse_block=64, sparse_stride=16, sparse_topk=64, sparse_init=1,
        sparse_local=32, sparse_dense_len=8192),
}


def init_hybrid_params(key: jax.Array, spec: HybridSpec) -> dict:
    """Random fp32 weights: Glorot matrices, unit gains, small non-zero
    sinks and router corrections (zeros would leave both inert), and an
    embedding of unit variance: a smaller one leaves the residual stream
    to the context's mean, every token then chooses the same few experts,
    and most of them are never chosen."""
    def leaf(k, name, shape):
        if name in ("ln1", "ln2", "qn", "kn", "on", "kvn"):
            return jnp.ones(shape, jnp.float32)
        if name == "embed":
            return jax.random.normal(k, shape, jnp.float32)
        if name in ("sink", "rc"):
            return 0.02 * jax.random.normal(k, shape, jnp.float32)
        limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(k, shape, jnp.float32, -limit, limit)

    keys = jax.random.split(key, spec.num_layers + 2)
    blocks = []
    for i in range(spec.num_layers):
        shapes = spec.block_shapes(i)
        ks = jax.random.split(keys[i], len(shapes))
        blocks.append({n: leaf(k, n, s)
                       for k, (n, s) in zip(ks, shapes.items())})
    e, v = spec.d_model, spec.vocab
    return {"embed": leaf(keys[-2], "embed", (v, e)), "blocks": blocks,
            "lnf": jnp.ones((e,), jnp.float32),
            "head": leaf(keys[-1], "head", (e, v))}


def rms_norm(x, g, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def yarn_freqs(spec: HybridSpec):
    """The ``rope_dim / 2`` rotary frequencies of a latent layer, fp32:
    ``theta_i = base ** (-2 i / rope_dim)``, left as they are below
    ``low``, divided by ``rope_factor`` from ``high`` on, a linear ramp
    between. ``d(beta) = rope_dim ln(original / (2 pi beta)) / (2 ln
    base)`` is the index whose wavelength turns ``beta`` times in the
    original context; ``low = floor(d(beta_fast))``, ``high =
    ceil(d(beta_slow))``, both kept inside the indices."""
    dim, base = spec.rope_dim, spec.rope_base_global
    theta = base ** (-jnp.arange(dim // 2, dtype=jnp.float32) * 2.0 / dim)
    if spec.rope_factor <= 1.0:
        return theta
    turn = lambda beta: dim * math.log(
        spec.rope_original / (2 * math.pi * beta)) / (2 * math.log(base))
    low = max(math.floor(turn(spec.rope_beta_fast)), 0)
    high = min(math.ceil(turn(spec.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return theta * (1.0 - ramp) + theta / spec.rope_factor * ramp


def partial_rope(x, positions, base: float, rotary_dim: int, freqs=None):
    """Rotate the first ``rotary_dim`` dimensions of each head of ``x [B,
    T, H, D]`` by ``positions [B, T]``, pairing ``(i, i + rotary_dim /
    2)``; the rest pass through. ``freqs [rotary_dim / 2]`` takes
    ``base``'s place where the kind has frequencies of its own."""
    half = rotary_dim // 2
    if freqs is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rotary_dim]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rotary_dim:]], -1)


def project(x, blk, spec: HybridSpec, layer: int, positions):
    """What a layer's kind hands its cache view, from the normed ``x [B,
    T, E]``: ``(q, k, v)`` heads, rotated and scaled; a latent layer
    gives ``q [B, T, Hq, nope + rope]``, its cached row ``[c | k_r]``
    as ``k [B, T, 1, kv_lora + rope]`` and no ``v``."""
    b, t, _ = x.shape
    heads = lambda a, d: a.reshape(b, t, -1, d)
    kind = spec.layer_kinds[layer]
    if kind == LATENT:
        freqs, rd = yarn_freqs(spec), spec.rope_dim
        rot = lambda a: partial_rope(a, positions, spec.rope_base_global, rd,
                                     freqs)
        q = heads(rms_norm(x @ blk["wqa"], blk["qn"], spec.norm_eps)
                  @ blk["wqb"], spec.nope_dim + rd)
        q = jnp.concatenate([q[..., :-rd], rot(q[..., -rd:])], -1)
        row = heads(x @ blk["wkva"], spec.latent_row)
        c = rms_norm(row[..., :-rd], blk["kvn"], spec.norm_eps)
        return q, jnp.concatenate([c, rot(row[..., -rd:])], -1), None
    if kind in GATED:
        q = rms_norm(heads(x @ blk["wq"], spec.head_dim), blk["qn"],
                     spec.norm_eps)
        k = rms_norm(heads(x @ blk["wk"], spec.head_dim), blk["kn"],
                     spec.norm_eps)
        if kind == LINEAR:
            rot = lambda a: partial_rope(a, positions, spec.rope_base_global,
                                         spec.head_dim)
            q = rot(q) * jnp.asarray(spec.head_dim ** -0.5, x.dtype)
            k = rot(k)
        return q, k, heads(x @ blk["wv"], spec.v_head_dim)
    base = spec.rope_base_window if kind == WINDOW else spec.rope_base_global
    q = partial_rope(heads(x @ blk["wq"], spec.head_dim), positions, base,
                     spec.rotary_dim)
    k = partial_rope(heads(x @ blk["wk"], spec.head_dim), positions, base,
                     spec.rotary_dim)
    v = heads(x @ blk["wv"], spec.v_head_dim) * jnp.asarray(
        spec.value_scale, x.dtype)
    return q, k, v


def latent_heads(blk, spec: HybridSpec):
    """``W_kvb`` read a head: ``(W_uk [kv_lora, H, nope], W_uv [kv_lora,
    H, v])``, the up-projections of ``c`` to a head's K and V."""
    w = blk["wkvb"].reshape(spec.kv_lora_rank, spec.num_heads, -1)
    return w[..., :spec.nope_dim], w[..., spec.nope_dim:]


def latent_kv(rows, blk, spec: HybridSpec):
    """The published form's K and V of cached rows ``rows [B, C, kv_lora
    + rope]``: ``c`` up-projected a head, ``k_r`` shared by all of them:
    ``(k [B, C, H, nope + rope], v [B, C, H, v])``."""
    w_uk, w_uv = latent_heads(blk, spec)
    c, k_r = rows[..., :spec.kv_lora_rank], rows[..., None, spec.kv_lora_rank:]
    k_n = jnp.einsum("bkc,chd->bkhd", c, w_uk)
    k = jnp.concatenate([k_n, jnp.broadcast_to(
        k_r, (*k_n.shape[:3], spec.rope_dim))], -1)
    return k, jnp.einsum("bkc,chd->bkhd", c, w_uv)


def latent_absorbed(q, blk, spec: HybridSpec, width: int, attend):
    """The absorbed form of a latent layer's attention: ``q [B, T, Hq,
    nope + rope]`` -> ``[B, T, Hq, v]``, with ``W_uk`` folded into the
    query and ``W_uv`` applied to the output, so every head reads the
    cached rows ``[c | k_r]`` as they lie: ONE K/V head whose V is the
    first ``kv_lora`` values of K. ``attend(qa [B, T, Hq, width]) -> [B,
    T, Hq, kv_lora]`` is that attention over rows ``width`` wide (a
    pool's, padded with zeros to whole lane tiles: the query is padded
    likewise), scores times ``spec.latent_scale``. The same attention as
    ``attend_grouped`` over :func:`latent_kv`'s K and V, and the cheaper
    one where the rows outnumber the queries by far."""
    w_uk, w_uv = latent_heads(blk, spec)
    qt = jnp.einsum("bthd,chd->bthc", q[..., :spec.nope_dim], w_uk)
    pad = jnp.zeros((*q.shape[:3], width - spec.latent_row), q.dtype)
    ot = attend(jnp.concatenate([qt, q[..., spec.nope_dim:], pad], -1))
    return jnp.einsum("bthc,chd->bthd", ot, w_uv)


def apply_block(h, blk, spec: HybridSpec, layer: int, positions, real, mix):
    """One layer on ``h [B, T, E]`` at ``positions [B, T]`` (``real [B,
    T]``: not padding). ``mix(layer, q, k, v)`` is the cache view: it
    keeps what :func:`project` hands it where it keeps it and returns
    the attention output ``[B, T, Hq, Dv]``. Returns ``(h, counts)``,
    ``counts`` the routed FFN's ``(assigned, touched)`` or ``None``."""
    b, t, _ = h.shape
    x = rms_norm(h, blk["ln1"], spec.norm_eps)
    a = mix(layer, *project(x, blk, spec, layer, positions)).reshape(b, t, -1)
    if spec.layer_kinds[layer] == LINEAR:
        a = rms_norm(a, blk["on"], spec.norm_eps)
    if spec.layer_kinds[layer] in GATED:
        a = a * jax.nn.sigmoid(x @ blk["wgate"])
    # a branch joins the stream times ``residual_scale``
    join = (lambda y: y) if spec.residual_scale == 1.0 else (
        lambda y: y * jnp.asarray(spec.residual_scale, y.dtype))
    h = h + join(a @ blk["wo"])
    x = rms_norm(h, blk["ln2"], spec.norm_eps)
    if spec.ffn_kinds[layer] == DENSE:
        up = jax.nn.silu(x @ blk["wg"]) * (x @ blk["wu"])
        return h + join(up @ blk["wd"]), None
    flat = x.reshape(b * t, -1)
    experts, weights = moe.route(flat, blk["wr"], blk["rc"],
                                 spec.experts_per_token, spec.route_scale)
    out, counts = moe.routed_ffn(
        flat, blk["eg"], blk["eu"], blk["ed"], experts, weights,
        real.reshape(-1), first=spec.experts_held[0],
        tile=moe.tile_rows(b * t, spec.experts_per_token, spec.num_experts))
    out = out.reshape(h.shape).astype(h.dtype)
    if spec.shared_ff:  # every token, whole, on every chip of the group
        out = out + (jax.nn.silu(x @ blk["sg"]) * (x @ blk["su"])) @ blk["sd"]
    return h + join(out), counts


def apply_layers(params, tokens, spec: HybridSpec, positions, real, mix,
                 compute_dtype=None):
    """Embedding, every block, the final norm: ``(h [B, T, E], counts
    int32 [2])``, ``counts`` summed over the routed layers."""
    h = params["embed"][tokens]
    if compute_dtype is not None:
        h = h.astype(compute_dtype)
    if spec.embed_scale != 1.0:
        h = h * jnp.asarray(spec.embed_scale, h.dtype)
    counts = jnp.zeros(2, jnp.int32)
    for i, blk in enumerate(params["blocks"]):
        h, c = apply_block(h, blk, spec, i, positions, real, mix)
        if c is not None:
            counts = counts + c
    h = rms_norm(h, params["lnf"], spec.norm_eps)
    if spec.logit_scale != 1.0:
        h = h * jnp.asarray(spec.logit_scale, h.dtype)
    return h, counts


def head_logits(params, h):
    return (h @ params["head"]).astype(jnp.float32)


def apply_hybrid(params, tokens, spec: HybridSpec, compute_dtype=None):
    """The uncached forward: ``tokens [B, T]`` -> ``(logits fp32 [B, T,
    V], counts)``. Attention over the call's own rows, nothing kept."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def mix(layer, q, k, v):
        kind = spec.layer_kinds[layer]
        if kind == LATENT:
            k, v = latent_kv(k[:, :, 0], params["blocks"][layer], spec)
            return kv_cache.attend_grouped(q, k, v, positions, positions,
                                           scale=spec.latent_scale)
        if kind == LINEAR:
            zero = jnp.zeros((spec.num_heads, spec.head_dim, spec.v_head_dim),
                             jnp.float32)
            rates = linear_attention.decay_rates(spec.num_heads)
            return jax.vmap(lambda a, b_, c: linear_attention.scan_chunks(
                zero, a, b_, c, rates, t)[0])(q, k, v)
        if kind == SPARSE:
            return sparse_over_own_rows(q, k, v, positions, spec)
        window = spec.layer_kinds[layer] == WINDOW
        return kv_cache.attend_grouped(
            q, k, v, positions, positions,
            window=spec.window if window else None,
            sink=params["blocks"][layer].get("sink"))

    h, counts = apply_layers(params, tokens, spec, positions,
                             jnp.ones((b, t), bool), mix, compute_dtype)
    return head_logits(params, h), counts


def sparse_over_own_rows(q, k, v, positions, spec: HybridSpec):
    """A sparse layer's attention over the call's own rows ``0 .. T -
    1``: the selector on the means of ``k``, then the masked form."""
    sel, scale = spec.selector, spec.head_dim ** -0.5
    blocks = -(-k.shape[1] // sel.block)
    means = sparse_attention.group_means(k, sel.stride)
    means = jnp.pad(means, ((0, 0), (0, blocks * sel.groups - means.shape[1]),
                            (0, 0), (0, 0)))
    allowed = sparse_attention.allowed_blocks(
        sparse_attention.select_blocks(q, means, positions, sel, scale),
        sparse_attention.attends_all(positions, sel))
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    return sparse_attention.attend_blocks(
        q, heads_first(k), heads_first(v), positions, allowed, sel.block,
        scale)


# -- the paged cache view -----------------------------------------------------
#
# Two page groups side by side. The GLOBAL group keeps every row: a slot's
# block table maps logical page j to a pool page, as the dense family's
# does, and a row's position is its logical row. A GLOBAL layer's pool is
# ONE array with the head before the row, ``[pages, Hkv, page_size, W]``, a
# row ``[k | zeros | v | zeros]`` (``ops.paged_attention.
# grouped_row_widths``); a LATENT layer's pool is of this group too, ONE
# pool of ``[c | k_r]`` rows (``serve.cache.latent_pool_width``). The
# WINDOW group keeps
# the last `window` rows: a slot's table is a ring of R columns, logical
# page j lives in column j % R, and the host frees a column's page once
# the page lies wholly behind the window. No position travels with a row:
# a column's logical page follows from the last position written, and
# whatever else a pool page still holds computes to a position behind the
# window or ahead of the query, which the mask drops.

GLOBAL_QUERY_BLOCK = 256


class PagedMixer:
    """The cache view of one prefill or decode call. ``pools`` maps a
    layer to what it keeps: a window layer's ``(k, v)`` pools, a global
    or a latent layer's ``(rows, None)``, a sparse layer's ``(rows, None,
    means)``, a linear
    layer's ``(None, None, state)``; after the layers have run it holds
    the updated ones. ``w_table`` is ``None`` where the pattern has no
    window layer; ``slot`` is the slot a prefill fills (a pattern with
    linear layers: its state is that slot's)."""

    def __init__(self, spec: HybridSpec, params, pools: dict, *, page_size,
                 g_table, w_table, positions, real, last, base=None,
                 slot=None, platform=None):
        self.spec, self.params, self.pools = spec, params, dict(pools)
        self.ps, self.real, self.slot, self.last = page_size, real, slot, last
        self.platform = platform or jax.default_backend()
        self.g_table, self.w_table = g_table, w_table
        self.positions = positions          # [B, T]; -1 where padding
        self.base = base                    # prefill: the block's first
        cols = jnp.arange(g_table.shape[1] * page_size, dtype=jnp.int32)
        self.g_pos = jnp.where(cols[None, :] <= last[:, None], cols[None, :],
                               -1)
        self.g_rows = kv_cache.table_rows(
            g_table, jnp.where(real, positions, -1), page_size,
            self._pages(LATENT))
        sparse = spec.layers_of(SPARSE)
        if sparse:  # the same pages; a head's K rows, then its V rows
            pages, heads, rows, _ = pools[sparse[0]][0].shape
            self.s_rows = [sparse_attention.head_major_rows(
                g_table, jnp.where(real, positions, -1), page_size, pages,
                heads, rows, first) for first in (0, page_size)]
        glob = spec.layers_of(GLOBAL)
        if glob:  # the same pages, a row a (head, token)
            pages, heads = pools[glob[0]][0].shape[:2]
            self.h_rows = sparse_attention.head_major_rows(
                g_table, jnp.where(real, positions, -1), page_size, pages,
                heads)
        if w_table is None:
            return
        keep = real & (positions > last[:, None] - spec.window)
        self.w_rows = kv_cache.ring_rows(
            w_table, jnp.where(keep, positions, -1), page_size,
            self._pages(WINDOW))
        self.w_pos = kv_cache.ring_positions(last, w_table.shape[1],
                                             page_size)

    def _pages(self, *kinds: int) -> int:
        """Pages of one group's pools, the group the layers of ``kinds``
        share (0 where the pattern has no such layer: its rows then never
        resolve to a write)."""
        layers = [i for kind in kinds for i in self.spec.layers_of(kind)]
        return self.pools[layers[0]][0].shape[0] if layers else 0

    def _write(self, layer, k, v, rows):
        """A pool row is all of a token's heads side by side (a whole
        number of 128-lane tiles at the published widths, so the scatter
        works on the pool as it lies in memory)."""
        pk, pv = self.pools[layer]
        flat = lambda a, pool: a.reshape(*a.shape[:2], -1).astype(pool.dtype)
        pk = kv_cache.write_rows_flat(pk, flat(k, pk), rows)
        pv = kv_cache.write_rows_flat(pv, flat(v, pv), rows)
        self.pools[layer] = (pk, pv)
        return pk, pv

    def _view(self, pool, table, like):
        """A slot's rows through its table, heads apart: ``[B, C, Hkv,
        D]`` in ``like``'s dtype and head width."""
        g = kv_cache.gather_pages(pool, table)
        return g.reshape(*g.shape[:2], -1, like.shape[-1]).astype(like.dtype)

    def __call__(self, layer, q, k, v):
        if self.spec.layer_kinds[layer] == GLOBAL:
            return self._global(layer, q, k, v)
        if self.spec.layer_kinds[layer] == LATENT:
            return self._latent(layer, q, k[:, :, 0])
        if self.spec.layer_kinds[layer] == LINEAR:
            return self._linear(layer, q, k, v)
        if self.spec.layer_kinds[layer] == SPARSE:
            return self._sparse(layer, q, k, v)
        if self.base is None:
            return self._window_decode(layer, q, k, v)
        return self._window_prefill(layer, q, k, v)

    def _global(self, layer, q, k, v):
        """Write the rows ``[k | zeros | v | zeros]`` into the layer's ONE
        pool, the head before the row, then attend the slot's whole
        table. Which form attends is chosen here, at trace time, from
        what the call shows: a decode tick (``base is None``) on a TPU,
        at widths ``ops.paged_attention.grouped_kernel_accepts``, reads
        each slot's mapped pages where they lie in the pool
        (``grouped_decode_attention``: nothing gathered, nothing re-laid
        out, the bytes of the pages resident); every other call gathers
        the table's pages into views and takes ``attend_grouped``, a
        prefill's queries in blocks so no ``[H, T, T]`` array exists."""
        spec = self.spec
        pool, _ = self.pools[layer]
        pool = sparse_attention.write_rows(
            pool, paged_attention.grouped_rows(k, v), self.h_rows)
        self.pools[layer] = (pool, None)
        hkv = pool.shape[1]
        if (self.base is None and self.platform == "tpu"
                and paged_attention.grouped_kernel_accepts(
                    spec.num_heads // hkv, spec.head_dim, spec.v_head_dim,
                    self.ps)):
            b = q.shape[0]
            out = paged_attention.grouped_decode_attention(
                q[:, 0].reshape(b, hkv, -1, spec.head_dim), pool,
                jnp.where(self.real, self.g_table, -1), self.positions[:, 0],
                v_head_dim=spec.v_head_dim)
            return out.reshape(b, 1, spec.num_heads, -1).astype(v.dtype)
        kv, vv = paged_attention.gather_grouped(
            pool, self.g_table, spec.head_dim, spec.v_head_dim)
        kv, vv = kv.astype(k.dtype), vv.astype(v.dtype)
        return self._in_blocks(q, lambda a, at: kv_cache.attend_grouped(
            a, kv, vv, at, self.g_pos))

    def _in_blocks(self, q, attend):
        """``attend(q, q_pos)`` over the call's queries; a prefill's go
        in blocks."""
        b, t = self.positions.shape
        blk = min(t, GLOBAL_QUERY_BLOCK)
        if b != 1 or t == blk:
            return attend(q, self.positions)
        qb = q.reshape(t // blk, 1, blk, *q.shape[2:])
        pb = self.positions.reshape(t // blk, 1, blk)
        out = lax.map(lambda a: attend(a[0], a[1]), (qb, pb))
        return out.reshape(1, t, *out.shape[3:])

    def _latent(self, layer, q, rows):
        """Write the rows ``[c | k_r]`` (padded to the pool's width, whole
        lane tiles), then attend. A decode tick reads each slot's table
        in the absorbed form, the rows as they lie: on a TPU, at widths
        ``ops.paged_attention.latent_kernel_accepts``, the pages where
        they are in the pool (``latent_decode_attention``, which moves
        the bytes of the pages resident); else a view gathered at the
        widest slot's bucket. A prefill block takes the published form,
        K and V up-projected once and the queries in blocks: from
        position 0 (a whole prompt, a first chunk) over its own rows, a
        later chunk over the slot's table, which holds them and the
        earlier chunks'."""
        pool, _ = self.pools[layer]
        spec, blk = self.spec, self.params["blocks"][layer]
        rows = rows.astype(pool.dtype)
        pool = kv_cache.write_rows_flat(pool, jnp.pad(rows, (
            (0, 0), (0, 0), (0, pool.shape[-1] - spec.latent_row))),
            self.g_rows)
        self.pools[layer] = (pool, None)
        if self.base is None:
            return latent_absorbed(q, blk, spec, pool.shape[-1],
                                   functools.partial(self._rows_as_they_lie,
                                                     pool))

        def over(rows, k_pos):
            k, v = latent_kv(rows, blk, spec)
            return self._in_blocks(q, lambda a, at: kv_cache.attend_grouped(
                a, k, v, at, k_pos, scale=spec.latent_scale))

        return lax.cond(self.base == 0,
                        lambda: over(rows, self.positions),
                        lambda: over(kv_cache.gather_pages(
                            pool, self.g_table)[..., :spec.latent_row],
                            self.g_pos))

    def _rows_as_they_lie(self, pool, qa):
        """A decode tick's queries ``qa [B, 1, Hq, W]`` over ONE K/V head
        of pool rows, V their first ``kv_lora`` values."""
        spec = self.spec
        kl, scale = spec.kv_lora_rank, spec.latent_scale
        if self.platform == "tpu" and paged_attention.latent_kernel_accepts(
                spec.num_heads, pool.shape[-1], kl, self.ps):
            return paged_attention.latent_decode_attention(
                qa[:, 0], pool, jnp.where(self.real, self.g_table, -1),
                self.positions[:, 0], scale=scale, v_width=kl)[:, None]
        kv = kv_cache.gather_pages(pool, self.g_table)[:, :, None, :]
        return kv_cache.attend_grouped(qa, kv, kv[..., :kl], self.positions,
                                       self.g_pos, scale=scale)

    def _linear(self, layer, q, k, v):
        """The layer's state ``[slots, H, Dk, Dv]`` (fp32), updated in
        place. A decode tick moves every active slot's one token on; a
        prefill block starts from zeros at position 0 and from the
        slot's own state past it (an earlier chunk's), and leaves the
        state after its last real row."""
        state = self.pools[layer][2]
        rates = linear_attention.decay_rates(self.spec.num_heads)
        if self.base is None:
            out, state = linear_attention.step(
                state, q[:, 0], k[:, 0], v[:, 0], rates, self.real[:, 0])
            out = out[:, None]
        else:
            start = lax.dynamic_index_in_dim(state, self.slot, keepdims=False)
            start = jnp.where(self.base == 0, 0.0, start)
            out, end = linear_attention.scan_chunks(
                start, q[0], k[0], v[0], rates, self.last[0] - self.base + 1)
            state = lax.dynamic_update_index_in_dim(state, end, self.slot, 0)
            out = out[None]
        self.pools[layer] = (None, None, state)
        return out

    def _sparse(self, layer, q, k, v):
        """Write the K and V rows (one pool: a head's K rows of a page,
        then its V rows) and the means of the groups they fill, then
        attend: a query within ``dense_len`` every row, one past it
        the blocks the selector picks from the slot's means. A decode
        tick on a TPU, at widths ``ops.sparse_attention.kernel_accepts``,
        reads the listed pages where they lie (``sparse_decode_
        attention``); else a view gathered at the widest slot's bucket
        and a block mask. A prefill block takes the masked form over the
        slot's pages (``attend_paged``)."""
        sel = self.spec.selector
        pool, _, means = self.pools[layer]
        for rows, new in zip(self.s_rows, (k, v)):
            pool = sparse_attention.write_rows(pool, new, rows)
        pos = self.positions
        if self.base is None:
            new = sparse_attention.pool_group_means(pool, self.g_table,
                                                    pos[:, 0], sel)
            first = jnp.where(pos >= 0, pos // sel.stride * sel.stride, -1)
        else:  # the block starts on a group's first row
            new = sparse_attention.group_means(k, sel.stride)
            first = jnp.where(self.real, pos, -1)[:, ::sel.stride]
        means = sparse_attention.write_means(means, new, first, self.g_table,
                                             sel, pool.shape[1])
        self.pools[layer] = (pool, None, means)
        attend = self._sparse_decode if self.base is None \
            else self._sparse_prefill
        return attend(q, pool, means)

    def _chosen(self, q, positions, seen):
        """The blocks each query picks from the slot's means as its table
        shows them (``seen``: ``table_means``)."""
        return sparse_attention.select_blocks(
            q, seen, positions, self.spec.selector,
            self.spec.head_dim ** -0.5)

    def _sparse_decode(self, q, pool, means):
        """One query a slot over a page list a (slot, K/V head): all of a
        slot's pages up to ``dense_len``, the chosen ones past it (no
        slot of a table within ``dense_len`` chooses)."""
        spec, sel, pos = self.spec, self.spec.selector, self.positions
        hkv, scale = pool.shape[1], spec.head_dim ** -0.5
        pages = self.g_table.shape[1]
        chosen = None if pages * sel.block <= sel.dense_len else self._chosen(
            q, pos, sparse_attention.table_means(means, self.g_table, sel,
                                                 hkv))[:, 0]
        listed = sparse_attention.listed_blocks(chosen, pos[:, 0], sel, pages,
                                                hkv)
        if self.platform == "tpu" and sparse_attention.kernel_accepts(
                spec.num_heads // hkv, spec.head_dim, self.ps):
            out = sparse_attention.sparse_decode_attention(
                q[:, 0].reshape(q.shape[0], hkv, -1, q.shape[-1]), pool,
                self.g_table, sparse_attention.pack_blocks(
                    listed, sel.list_width(pages)), pos[:, 0], scale=scale)
            return out.reshape(q.shape[0], 1, spec.num_heads, -1)
        kv, vv = sparse_attention.gather_heads(pool, self.g_table)
        return sparse_attention.attend_blocks(
            q, kv.astype(q.dtype), vv.astype(q.dtype), pos, listed[:, None],
            sel.block, scale)

    def _sparse_prefill(self, q, pool, means):
        """One slot's block in the masked form; the selector runs, in
        query blocks, only where the block's context passes
        ``dense_len``."""
        sel, (_, t) = self.spec.selector, self.positions.shape
        pages, hkv = self.g_table.shape[1], pool.shape[1]
        qb = math.gcd(t, GLOBAL_QUERY_BLOCK)
        blocks = lambda a: a.reshape(t // qb, qb, *a.shape[1:])

        def picked():
            seen = sparse_attention.table_means(means, self.g_table, sel, hkv)
            return lax.map(
                lambda a: sparse_attention.allowed_blocks(
                    self._chosen(a[0][None], a[1][None], seen)[0],
                    sparse_attention.attends_all(a[1], sel)),
                (blocks(q[0]), blocks(self.positions[0])))

        allowed = lax.cond(self.last[0] >= sel.dense_len, picked,
                           lambda: jnp.ones((t // qb, qb, hkv, pages), bool))
        return sparse_attention.attend_paged(
            q[0], pool, self.g_table[0], self.positions[0],
            allowed.reshape(t, hkv, pages), sel.block,
            self.spec.head_dim ** -0.5)[None]

    def _window_decode(self, layer, q, k, v):
        pk, pv = self._write(layer, k, v, self.w_rows)
        return kv_cache.attend_grouped(
            q, self._view(pk, self.w_table, k), self._view(pv, self.w_table, v),
            self.positions, self.w_pos, window=self.spec.window,
            sink=self.params["blocks"][layer]["sink"])

    def _window_prefill(self, layer, q, k, v):
        """One slot's block of ``T`` rows on the band: a query block of
        one window against its own rows and the window before them, the
        first block's from the ring as it stood before this call. Only
        the last ``window`` rows are written."""
        w, ps = self.spec.window, self.ps
        pk, pv = self.pools[layer]
        t = q.shape[1]
        old = self.base - w + jnp.arange(w, dtype=jnp.int32)  # positions
        at = jnp.maximum(old, 0)
        idx = (at // ps) % self.w_table.shape[1] * ps + at % ps
        ek = jnp.concatenate([self._view(pk, self.w_table, k)[0, idx], k[0]])
        ev = jnp.concatenate([self._view(pv, self.w_table, v)[0, idx], v[0]])
        epos = jnp.concatenate([jnp.where(old >= 0, old, -1),
                                self.positions[0]])
        self._write(layer, k, v, self.w_rows)
        sink = self.params["blocks"][layer]["sink"]
        if t <= w:
            return kv_cache.attend_grouped(
                q, ek[None], ev[None], self.positions, epos[None],
                window=w, sink=sink)
        nb = t // w

        def band(a):
            a = a.reshape(nb + 1, w, *a.shape[1:])
            return jnp.concatenate([a[:-1], a[1:]], axis=1)    # [nb, 2W, ..]

        out = kv_cache.attend_grouped(
            q.reshape(nb, w, *q.shape[2:]), band(ek), band(ev),
            self.positions.reshape(nb, w), band(epos), window=w, sink=sink)
        return out.reshape(1, t, *out.shape[2:])


def apply_hybrid_paged(params, pools: dict, tokens, spec: HybridSpec, *,
                       page_size: int, g_table, w_table, positions, real,
                       last, base=None, slot=None, compute_dtype=None,
                       platform=None):
    """The serving forward of one call through :class:`PagedMixer`:
    ``tokens [B, T]`` at ``positions [B, T]`` -> ``(h [B, T, E], pools,
    counts)``. ``base`` (a traced scalar) marks a prefill of one slot
    from that position; ``None`` a decode of one row a slot. ``last [B]``
    is the last position this call writes (behind 0: nothing).
    ``w_table`` is ``None`` for a pattern without window layers;
    ``slot`` (a traced scalar) is the slot a prefill fills, read by
    linear layers alone.
    ``platform`` is the platform of the devices the program will run on
    (the default backend's when not given): a latent layer's decode
    and a global layer's decode read their pages in place on a TPU."""
    mix = PagedMixer(spec, params, pools, page_size=page_size,
                     g_table=g_table, w_table=w_table, positions=positions,
                     real=real, last=last, base=base, slot=slot,
                     platform=platform)
    h, counts = apply_layers(params, tokens, spec,
                             jnp.maximum(positions, 0), real, mix,
                             compute_dtype)
    return h, mix.pools, counts
