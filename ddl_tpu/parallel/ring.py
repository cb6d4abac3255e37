"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context support beyond the reference's feature matrix (the reference
has no attention and no sequence axis at all — fixed 784-pixel images,
mnist_sync/model/model.py:18-19; SURVEY.md §5 records sequence
parallelism as owed nothing for parity). This module adds the two
standard TPU-native sequence-parallel schemes as first-class mesh
programs, so models with a sequence dimension scale past one chip's HBM:

- **Ring attention** (:func:`ring_attention_shard`): Q stays resident;
  K/V blocks rotate around the mesh axis via ``lax.ppermute`` (ICI
  neighbour links — the mesh axis follows the physical torus, see
  ``mesh.make_mesh``). Attention is EXACT: the streaming-softmax state
  ``(m, l, acc)`` is rescaled per block (the FlashAttention/online-softmax
  recurrence), so P ring steps reproduce full softmax over the whole
  sequence while each device only ever materializes a ``[Tq_local,
  Tk_local]`` score tile. Memory per device: O(T/P) sequence, O(T/P * T/P)
  scores — the whole point of the scheme.
- **Ulysses / all-to-all** (:func:`ulysses_attention_shard`): two
  ``lax.all_to_all``s re-partition sequence-sharded activations to
  head-sharded ones and back; attention itself is an ordinary full-
  sequence computation over each device's head subset. Cheaper in
  collective count when ``num_heads >= P``; requires ``num_heads % P == 0``.

Causal ring sweeps support two position layouts: the contiguous default
(block ``i`` on device ``i`` — simple, but device P-1 computes on every
ring step) and the balanced two-ended **zigzag** layout
(:func:`zigzag_positions` / :func:`zigzag_permutation` — device ``i``
holds chunks ``i`` and ``2P-1-i`` of ``2P``, sub-tile skipping halves
the causal critical path; :func:`causal_work_profile` quantifies both).

Both are pure per-shard functions for use inside ``shard_map`` (the same
contract as ``collectives.py``), plus jitted whole-array wrappers
(:func:`make_ring_attention`, :func:`make_ulysses_attention`) that place
global ``[B, T, H, D]`` arrays sequence-sharded over the mesh axis.
Causal masking uses absolute positions (``lax.axis_index`` offsets), and
the ring starts on each device's own diagonal block so a causal sweep
never sees an all-masked first tile (the streaming state would otherwise
need NaN guards for ``exp(-inf - -inf)``).

Tests pin both schemes (fwd + grad, causal and not) against a
single-device oracle on the 8-device virtual mesh: tests/test_ring.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DP_AXIS

_MASKED = -1e30  # large-negative (not -inf): keeps exp(s - m) NaN-free


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    scale: float | None = None, q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
) -> jax.Array:
    """Plain softmax attention, ``[B, T, H, D]`` — the single-device oracle
    and the local kernel inside the Ulysses scheme. ``q_offset``/``k_offset``
    are the absolute positions of element 0 (needed when the caller holds a
    shard of the sequence), so causal masking is correct under sharding."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def zigzag_positions(
    i: int | jax.Array, axis_size: int, t_local: int
) -> jax.Array:
    """Absolute positions of shard ``i``'s rows under the two-ended
    ("zigzag") causal layout: the sequence is cut into ``2P`` equal
    chunks and device ``i`` holds chunks ``i`` and ``2P-1-i`` — one from
    each end of the causal triangle, so every device owns the same
    amount of early (cheap) and late (expensive) causal work. ``i`` may
    be a traced ``lax.axis_index``. This is the ONE definition of the
    layout — the staging permutation and the analytic work profile both
    derive from it (with ``numpy`` passed for host-side math)."""
    return _zigzag_positions(i, axis_size, t_local, jnp)


def _zigzag_positions(i, axis_size: int, t_local: int, xp):
    """Backend-generic body: ``xp`` is ``jnp`` (traced, in-shard) or
    ``numpy`` (host staging / analysis) — one source of truth for the
    chunk-pair assignment."""
    if t_local % 2:
        raise ValueError(
            f"zigzag layout needs an even per-shard length, got {t_local}"
        )
    h = t_local // 2
    lo = i * h + xp.arange(h)
    hi = (2 * axis_size - 1 - i) * h + xp.arange(h)
    return xp.concatenate([lo, hi])


def zigzag_permutation(axis_size: int, seq_len: int):
    """Host-side gather index ``perm [seq_len]`` such that contiguous
    sharding of ``x[..., perm]`` over ``axis_size`` devices lands the
    zigzag chunk pair ``(i, 2P-1-i)`` on device ``i`` — i.e. slot ``t``
    of the permuted sequence holds original position
    ``zigzag_positions(t // t_local, P, t_local)[t % t_local]`` (derived
    from that same function, so staging can never diverge from the
    in-shard position math). Pure numpy — staging-time data movement,
    not a mesh op."""
    import numpy as np

    if seq_len % (2 * axis_size):
        raise ValueError(
            f"zigzag layout needs seq_len % (2 * {axis_size}) == 0, "
            f"got {seq_len}"
        )
    t_local = seq_len // axis_size
    return np.concatenate([
        _zigzag_positions(i, axis_size, t_local, np)
        for i in range(axis_size)
    ]).astype(np.int64)


def causal_work_profile(
    axis_size: int, layout: str = "contiguous"
) -> "np.ndarray":
    """Analytic per-(device, ring step) compute for a causal ring sweep,
    in units of ONE FULL local tile — the same fully-masked-skip rule
    the runtime ``lax.cond`` applies, evaluated on the layout's position
    assignment. Returns ``work [P, P]``; ``work[i, r]`` is what device
    ``i`` computes at ring step ``r``. The wall-clock critical path of
    the lockstep ring is ``sum_r max_i work[i, r]`` (every step waits on
    its busiest device at the ppermute): contiguous = P full tiles
    (device P-1 computes every step); zigzag = (2P+1)/4 — the balanced
    layout halves the causal critical path. Used by tests and the
    balance bench row; unit-tested against the actual skip behavior."""
    import numpy as np

    P_ = axis_size
    nsub = 2 if layout == "zigzag" else 1
    t_local = 2 * nsub  # smallest even per-shard length; work is scale-free
    if layout == "zigzag":
        pos = [_zigzag_positions(i, P_, t_local, np) for i in range(P_)]
    elif layout == "contiguous":
        pos = [i * t_local + np.arange(t_local) for i in range(P_)]
    else:
        raise ValueError(f"unknown layout {layout!r}")
    ns = t_local // nsub
    work = np.zeros((P_, P_))
    for i in range(P_):
        for r in range(P_):
            j = (i - r) % P_  # origin of the K/V block held at step r
            for a in range(nsub):
                qp = pos[i][a * ns:(a + 1) * ns]
                for b in range(nsub):
                    kp = pos[j][b * ns:(b + 1) * ns]
                    if kp.min() <= qp.max():  # the runtime skip rule
                        work[i, r] += 1.0 / (nsub * nsub)
    return work


def ring_attention_shard(
    q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str,
    axis_size: int, causal: bool = False, scale: float | None = None,
    qpos: jax.Array | None = None, kpos: jax.Array | None = None,
    vary_axes: tuple[str, ...] | None = None,
    layout: str = "contiguous", nsub: int | None = None,
) -> jax.Array:
    """Exact attention over a sequence sharded along ``axis_name``; call
    INSIDE ``shard_map``. Per-shard shapes ``[B, T/P, H, D]``.

    P ring steps; at step r this device holds the K/V block that started
    on device ``(i - r) % P`` (blocks rotate ``i -> i+1`` via
    ``ppermute`` — neighbour traffic on ICI). The online-softmax state is
    carried in fp32 regardless of input dtype; output is cast back to
    ``q.dtype``.

    ``qpos``/``kpos`` are the ABSOLUTE sequence positions of this shard's
    rows (int32 ``[Tq]`` / ``[Tk]``; default: per ``layout``). ``kpos``
    travels around the ring with its K/V block, so any assignment of
    positions to devices is supported — custom layouts just pass their
    own position arrays. ``layout`` names the built-in assignments:

    - ``"contiguous"`` (default): block ``i`` in mesh order. Simple, but
      a causal sweep leaves device P-1 computing on every ring step
      while device 0 computes once — the critical path is P full tiles.
    - ``"zigzag"``: the two-ended assignment (:func:`zigzag_positions`) —
      device ``i`` holds chunks ``i`` and ``2P-1-i`` of ``2P``. With the
      sub-tile skip below, every device computes ~2 quarter-tiles per
      ring step (3 on its diagonal step): the causal critical path drops
      to (2P+1)/4 full tiles, ~2x faster than contiguous at large P
      (:func:`causal_work_profile`). The CALLER owns the matching data
      movement: shard ``x[..., zigzag_permutation(P, T)]`` contiguously
      (strategies/seq.py stages exactly that, and feeds the same
      positions to RoPE so rotations stay absolute).

    Causal sub-tiles that are ENTIRELY masked (``min(kpos_sub) >
    max(qpos_sub)``, checked at runtime per ring step) skip their
    score/update compute via ``lax.cond``. ``nsub`` is the skip
    granularity: each local block is processed as ``nsub`` q-chunks x
    ``nsub`` travelling k-chunks (default 1; zigzag defaults to 2 —
    chunk-pair granularity, which is what makes its balance real: at
    tile granularity a zigzag tile always contains SOME unmasked work
    and nothing would skip). A skipped-from-the-start state is clean
    (the first real block's correction factor is exp(_MASKED - m_new)
    = 0), but every causal query row must attend at least one key (true
    whenever position 0 is somewhere in ``kpos``'s global set), or its
    normalization hits 0/0.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    i = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if nsub is None:
        # Sub-tiling exists only for the causal skip: without causality
        # nothing can ever skip, so splitting would just shrink the MXU
        # tiles for zero benefit.
        nsub = 2 if (layout == "zigzag" and causal) else 1
    if qpos is None:
        qpos = (zigzag_positions(i, axis_size, Tq) if layout == "zigzag"
                else i * Tq + jnp.arange(Tq))
    if kpos is None:
        kpos = (zigzag_positions(i, axis_size, Tk) if layout == "zigzag"
                else i * Tk + jnp.arange(Tk))
    if Tq % nsub or Tk % nsub:
        raise ValueError(
            f"per-shard lengths ({Tq}, {Tk}) not divisible by nsub={nsub}"
        )

    # pcast-to-varying: the init state must carry the mesh axes in its
    # varying set, or the causal lax.cond rejects identity-vs-update
    # branches (the identity branch would return the axis-invariant init
    # while block_update's outputs vary with this device's q/k). On a
    # multi-axis mesh where q/k/v vary over MORE than the ring axis
    # (e.g. batch sharded over dp while the ring runs over sp), pass
    # ``vary_axes`` with every axis the inputs vary over.
    vary = functools.partial(
        lax.pcast, axis_name=vary_axes or axis_name, to="varying"
    )
    nq, nk = Tq // nsub, Tk // nsub
    # Per-q-chunk streaming state (python lists — nsub is static and tiny).
    qs = [q[:, a * nq:(a + 1) * nq] for a in range(nsub)]
    qps = [lax.slice(qpos, (a * nq,), ((a + 1) * nq,)) for a in range(nsub)]
    qmaxs = [qp.max() for qp in qps]
    ms = [vary(jnp.full((B, H, nq), _MASKED, dtype=jnp.float32))
          for _ in range(nsub)]
    ls = [vary(jnp.zeros((B, H, nq), dtype=jnp.float32)) for _ in range(nsub)]
    accs = [vary(jnp.zeros((B, nq, H, D), dtype=jnp.float32))
            for _ in range(nsub)]
    perm = [(s, (s + 1) % axis_size) for s in range(axis_size)]

    def block_update(m, l, acc, q, qpos, k, v, kpos):
        # Scores leave the MXU in fp32 (its accumulator's own dtype),
        # never rounded to the inputs' bf16 first. Not only precision: on
        # TPU, differentiating a bf16-OUTPUT dot inside the causal skip's
        # lax.cond returns all-NaN dq/dk (v5e, jaxlib 0.9.0; the same
        # body outside a conditional, or with this fp32 output, is exact
        # — found by chip_smoke.py's 2x2 ring phase, PR 21).
        s_tile = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        s_tile = s_tile * scale
        if causal:
            s_tile = jnp.where(
                kpos[None, :] <= qpos[:, None], s_tile, _MASKED
            )
        m_new = jnp.maximum(m, s_tile.max(axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(s_tile - m_new[..., None])
        l = l * correction + p.sum(axis=-1)
        acc = acc * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
        )
        return m_new, l, acc

    for r in range(axis_size):
        for b in range(nsub):
            k_sub = k[:, b * nk:(b + 1) * nk]
            v_sub = v[:, b * nk:(b + 1) * nk]
            kp_sub = lax.slice(kpos, (b * nk,), ((b + 1) * nk,))
            kmin = kp_sub.min() if causal else None
            for a in range(nsub):
                if causal:
                    # Entirely-future sub-tiles do no work (runtime check
                    # on the travelling positions — correct for ANY
                    # layout, including Tk != Tq). The saving is
                    # per-device compute; ring steps stay lockstep at the
                    # ppermute, so wall-clock balance depends on the
                    # position LAYOUT (see the docstring / zigzag).
                    ms[a], ls[a], accs[a] = lax.cond(
                        kmin > qmaxs[a],
                        lambda m, l, acc, q, qpos, k, v, kpos: (m, l, acc),
                        block_update,
                        ms[a], ls[a], accs[a], qs[a], qps[a],
                        k_sub, v_sub, kp_sub,
                    )
                else:
                    ms[a], ls[a], accs[a] = block_update(
                        ms[a], ls[a], accs[a], qs[a], qps[a],
                        k_sub, v_sub, kp_sub,
                    )
        if r != axis_size - 1:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            if causal:
                kpos = lax.ppermute(kpos, axis_name, perm)
    acc = jnp.concatenate(accs, axis=1)
    l = jnp.concatenate(ls, axis=2)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention_shard(
    q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str,
    axis_size: int, causal: bool = False, scale: float | None = None,
    local_attn=None,
) -> jax.Array:
    """Ulysses sequence parallelism; call INSIDE ``shard_map``. Per-shard
    ``[B, T/P, H, D]`` with ``H % P == 0``: one ``all_to_all`` turns the
    sequence sharding into a head sharding ``[B, T, H/P, D]``, a plain
    full-sequence local kernel runs on the head subset, and a second
    ``all_to_all`` restores sequence sharding. ``local_attn`` overrides
    the kernel — a ``(q, k, v) -> out`` closure over full-sequence
    ``[B, T, H/P, D]`` with causality/scale already bound (e.g. the
    Pallas flash kernel, ops/attention.py); default
    :func:`full_attention`."""
    H = q.shape[2]
    if H % axis_size:
        raise ValueError(
            f"ulysses needs num_heads % axis_size == 0, got {H} % {axis_size}"
        )
    if local_attn is None:
        local_attn = functools.partial(
            full_attention, causal=causal, scale=scale
        )
    a2a = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    back = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=1, concat_axis=2,
        tiled=True,
    )
    out = local_attn(a2a(q), a2a(k), a2a(v))
    return back(out)


def seq_sharding(mesh: Mesh, axis: str = DP_AXIS) -> NamedSharding:
    """The ``[B, T, H, D]`` sequence-sharded placement both wrappers
    expect — ``jax.device_put(x, seq_sharding(mesh))`` stages inputs
    without relying on the jit boundary to insert the transfer."""
    return NamedSharding(mesh, P(None, axis))


def _make_wrapper(shard_fn, mesh: Mesh, axis: str, causal: bool):
    P_ = mesh.shape[axis]
    spec = P(None, axis)

    @jax.jit
    def fn(q, k, v):
        return jax.shard_map(
            functools.partial(
                shard_fn, axis_name=axis, axis_size=P_, causal=causal
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # Every spec is sharded, so the replication checker has
            # nothing to certify here — and pre-vma JAX's checker has no
            # rule for the causal sweep's lax.cond ("branches of cond
            # produced mismatched replication types"). Gradients through
            # this boundary ride ppermute/all_to_all transposes only
            # (exact on every generation), never a psum.
            check_vma=False,
        )(q, k, v)

    return fn


def make_ring_attention(
    mesh: Mesh, *, axis: str = DP_AXIS, causal: bool = False
):
    """Jitted ring attention over global ``[B, T, H, D]`` arrays sharded
    on ``T`` along ``mesh``'s ``axis`` (``T % mesh.shape[axis] == 0``).
    Use :func:`jax.device_put` with ``NamedSharding(mesh, P(None, axis))``
    to place inputs (the wrapper's jit will otherwise insert the
    placement transfer itself)."""
    return _make_wrapper(ring_attention_shard, mesh, axis, causal)


def make_ulysses_attention(
    mesh: Mesh, *, axis: str = DP_AXIS, causal: bool = False
):
    """Jitted Ulysses attention over global ``[B, T, H, D]`` arrays
    sharded on ``T`` (``T`` and ``H`` both divisible by the axis size)."""
    return _make_wrapper(ulysses_attention_shard, mesh, axis, causal)
