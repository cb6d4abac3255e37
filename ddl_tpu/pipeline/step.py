"""The pipeline train/eval step bodies — ``shard_map`` programs over the
4-D ``[dp, sp, tp, pp]`` mesh (``parallel.mesh.make_mesh_4d``).

One ``lax.scan`` over schedule TICKS executes any (GPipe or 1F1B) table
pair from ``pipeline.schedule``. Every tick, every pp position runs the
SAME masked SPMD body — one stage FORWARD slot and one stage BACKWARD
slot — and two ``ppermute``s hop the tick's products along the pp axis:
the forward slot's activation to stage ``s+1``, the backward slot's
input-cotangent to stage ``s-1``. Idle slots compute on junk and mask
the results (uniform SPMD: per-stage control flow does not exist inside
``shard_map``, and a traced ``lax.cond`` lowers to ``select`` anyway),
so wall time is proportional to TICK COUNT — which is exactly what
makes the schedule's bubble fraction measurable
(``benchmarks/pipeline_bubble.py``).

The backward is MANUAL — per-microbatch ``jax.vjp`` recompute from the
saved stage INPUT (activation-recompute pipelining: per in-flight
microbatch a stage holds one ``[mb, T, E]`` input, never the attention
residuals) — so no gradient ever rides an autodiff transpose of
``ppermute``/``psum`` whose rule varies across JAX generations
(the same explicit-gradient discipline as
``collectives.tp_allreduce``). Megatron tensor parallelism composes
INSIDE the stage unchanged: ``jax.vjp`` honours the f/g ``custom_vjp``
pair, so tp's activation psums run in lockstep across the tp axis at
every tick.

Loss discipline matches ``strategies.seq._local_loss_fn``: each device
accumulates its own scored-token CE sum over the GLOBAL (psum'd) weight
total; every microbatch backward seeds with ``1/global_den``; gradients
stay LOCAL until ONE explicit reduction at step end — ``psum`` over
(dp, sp) for the stage-resident block stack, ``psum`` over (dp, sp, pp)
for the pp-replicated embed/head/final-LN leaves (exactly one stage
contributes nonzero; the psum doubles as the broadcast).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..models import transformer
from ..ops import adam_update
from ..parallel import collectives as coll
from ..parallel.mesh import DP_AXIS, PP_AXIS, SP_AXIS, TP_AXIS

# The data axes every pipeline loss/grad reduction runs over (sp is
# size 1 under pipeline parallelism — kept so the specs and psums stay
# word-for-word the 2-D trainer's).
AXES = (DP_AXIS, SP_AXIS)

# pp-replicated leaves of the pipeline param tree: owned by stage 0
# (embed) / the last stage (final-LN, head), zero-gradient everywhere
# else, reduced over (dp, sp, pp) instead of (dp, sp).
SHARED_LEAVES = ("embed", "head", "lnf_g", "lnf_b")


def _local_attn(config, platform):
    """Per-stage local attention: the sequence is WHOLE on every device
    under pipeline parallelism (``validate_topology`` enforces
    num_workers == 1 and scheme='full'), so this is exactly the
    scheme='full' branch of ``strategies.seq._attn_for`` — reused, not
    re-implemented, so kernel selection (xla/flash, platform gating) can
    never fork between the pipeline and the oracle it is pinned
    against. Lazy import: seq imports this package only inside methods,
    so there is no cycle either way, but keeping both directions lazy
    makes import order irrelevant."""
    from ..strategies.seq import _attn_for

    return _attn_for(config, platform)


def make_stage_fn(config, platform):
    """Build the per-stage forward closure:

    ``stage_fn(params, h_in, tokens, targets, weights, first)
    -> (h_out, ce_num)``

    ``params`` is the PIPELINE (stacked-blocks) tree; the body applies
    THIS device's local layer shard ``[L/pp, ...]`` sequentially via
    :func:`transformer.apply_block` (the oracle's exact layer unit).
    ``first`` (a traced bool — ``axis_index(PP_AXIS) == 0``) selects the
    embedding of ``tokens`` over ``h_in`` as the stage input, so the
    embed gradient is EXACTLY zero off stage 0 (the ``where`` transpose
    zeroes the unselected branch). Every stage also runs the final-LN /
    head / CE tail; only the LAST stage's ``ce_num`` is accumulated (and
    only its backward seeds it), so head/lnf grads are exactly zero off
    the last stage. One definition serves the forward slot, the
    backward slot's ``jax.vjp`` recompute, and (minus the loss tail)
    eval — the pipeline can never drift from its own backward."""
    spec = config.spec
    attn = _local_attn(config, platform)
    tp = config.tensor_parallel
    reduce_ = coll.tp_allreduce(TP_AXIS) if tp > 1 else None
    promote = coll.tp_promote(TP_AXIS) if tp > 1 else None

    def blocks_fwd(p_blocks, h, positions):
        def blk_fn(h, blk):
            return transformer.apply_block(
                h, blk, spec, attn_fn=attn, positions=positions,
                row_reduce=reduce_, col_promote=promote,
            )

        if config.remat:
            blk_fn = jax.checkpoint(blk_fn)
        l_local = jax.tree.leaves(p_blocks)[0].shape[0]
        for i in range(l_local):
            h = blk_fn(h, jax.tree.map(lambda a: a[i], p_blocks))
        return h

    def stage_fn(params, h_in, tokens, targets, weights, first):
        p = params
        if config.dtype() is not None:
            p = jax.tree.map(lambda a: a.astype(config.dtype()), dict(p))
        positions = jnp.arange(tokens.shape[1])
        h = jnp.where(first, p["embed"][tokens].astype(h_in.dtype), h_in)
        h = blocks_fwd(p["blocks"], h, positions)
        hl = transformer._layernorm(h, p["lnf_g"], p["lnf_b"])
        logits = (hl @ p["head"]).astype(jnp.float32)
        num, _ = transformer.ce_sums(logits, targets, weights)
        return h, num

    return stage_fn, blocks_fwd


def make_pipeline_step_body(config, part, tables, platform, *, lr,
                            health: bool = False, guard: bool = False):
    """One pipeline train step, already inside ``shard_map``
    (``check_vma=False``, local-grads mode):
    ``(params, opt, tokens, targets, weights) -> (params, opt, loss)``.

    ``tables`` is the ``(f_tab, b_tab)`` pair from
    ``pipeline.schedule``; the scan's per-tick carry holds three small
    activation ring buffers sized by ``schedule.buffer_slots`` —
    ``save`` (stage inputs awaiting backward: M slots under GPipe,
    min(pp, M) under 1F1B — the schedules' memory difference, realized
    as a static buffer shape), ``inbox`` (arrived activations), and
    ``ctbox`` (arrived cotangents) — plus the gradient accumulators and
    the CE-sum accumulator. Microbatch gradient accumulation feeds the
    SAME TF1-Adam update every other mode applies, on optimizer state
    placed like the pipeline params (block m/v stage-resident over pp,
    tp-sharded over tp)."""
    f_tab, b_tab = tables
    pp = part.pp
    m = int(f_tab.max()) + 1
    # Precision policy (ddl_tpu.precision): under "bf16" the step-end
    # gradient psums move bf16 bytes and the Adam boundary upcasts to
    # fp32 (master weights + m/v stay fp32); both hooks are
    # Python-level no-ops for fp32/legacy configs — the exact
    # pre-policy program.
    pol = config.policy()
    from .schedule import buffer_slots

    slots = buffer_slots(f_tab, b_tab)
    q_save, q_in, q_ct = slots["save"], slots["inbox"], slots["ctbox"]
    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
    bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]
    stage_fn, _ = make_stage_fn(config, platform)
    act_dtype = config.dtype() or jnp.float32
    e = config.spec.d_model

    def step(params, opt_state, tokens, targets, weights):
        s_idx = lax.axis_index(PP_AXIS)
        first = s_idx == 0
        last = s_idx == pp - 1
        b_loc, t_seq = tokens.shape
        mb = b_loc // m
        xs = tokens.reshape(m, mb, t_seq)
        ys = targets.reshape(m, mb, t_seq)
        ws = weights.reshape(m, mb, t_seq)
        # Global scored-weight total: no param dependence, so dividing
        # by it keeps gradients LOCAL (the _local_loss_fn discipline).
        den = lax.psum(jnp.sum(weights.astype(jnp.float32)), AXES)
        inv_den = 1.0 / den

        buf = lambda q: jnp.zeros((q, mb, t_seq, e), act_dtype)
        carry0 = (
            buf(q_in), buf(q_save), buf(q_ct),
            jax.tree.map(jnp.zeros_like, params),
            jnp.float32(0.0),
        )

        def tick(carry, cols):
            in_buf, save_buf, ct_buf, gacc, num_acc = carry
            f_col, b_col = cols
            f_m = f_col[s_idx]
            b_m = b_col[s_idx]
            is_f = f_m >= 0
            is_b = b_m >= 0
            fi = jnp.maximum(f_m, 0)
            bi = jnp.maximum(b_m, 0)
            # Reads before writes: the B slot's saved input/cotangent
            # predate this tick by construction of the tables.
            h_in = in_buf[fi % q_in]
            h_saved = save_buf[bi % q_save]
            ct_in = ct_buf[bi % q_ct]

            # ---- forward slot (junk when idle; every result masked)
            h_out, num = stage_fn(params, h_in, xs[fi], ys[fi], ws[fi],
                                  first)
            save_buf = save_buf.at[fi % q_save].set(
                jnp.where(is_f, h_in, save_buf[fi % q_save])
            )
            num_acc = num_acc + jnp.where(is_f & last, num, 0.0)

            # ---- backward slot: vjp-recompute from the saved stage
            # input. The last stage seeds from the loss (d loss/d num =
            # 1/global_den); every other stage seeds from the arrived
            # cotangent of its stage OUTPUT.
            _, vjp_fn = jax.vjp(
                lambda p, h: stage_fn(p, h, xs[bi], ys[bi], ws[bi], first),
                params, h_saved,
            )
            ct_h = jnp.where(last, jnp.zeros_like(ct_in), ct_in)
            ct_num = jnp.where(last, inv_den, 0.0)
            d_params, d_h = vjp_fn((ct_h.astype(h_saved.dtype), ct_num))
            bmask = is_b.astype(jnp.float32)
            gacc = jax.tree.map(lambda a, g: a + bmask * g, gacc, d_params)

            # ---- stage hops: tick-end ppermutes; arrivals are stored
            # into the ring buffers for the ticks that consume them.
            # The cyclic wrap (last stage -> stage 0 forward, stage 0 ->
            # last backward) is masked out at the receiver.
            h_arr = lax.ppermute(
                jnp.where(is_f, h_out, jnp.zeros_like(h_out))
                .astype(act_dtype),
                PP_AXIS, fwd_perm,
            )
            ct_arr = lax.ppermute(
                jnp.where(is_b, d_h, jnp.zeros_like(d_h)).astype(act_dtype),
                PP_AXIS, bwd_perm,
            )
            src_f = f_col[(s_idx - 1) % pp]
            sf = jnp.maximum(src_f, 0) % q_in
            in_buf = in_buf.at[sf].set(
                jnp.where((src_f >= 0) & ~first, h_arr, in_buf[sf])
            )
            src_b = b_col[(s_idx + 1) % pp]
            sb = jnp.maximum(src_b, 0) % q_ct
            ct_buf = ct_buf.at[sb].set(
                jnp.where((src_b >= 0) & ~last, ct_arr, ct_buf[sb])
            )
            return (in_buf, save_buf, ct_buf, gacc, num_acc), None

        cols = (jnp.asarray(f_tab.T), jnp.asarray(b_tab.T))  # [T, pp]
        (_, _, _, gacc, num_acc), _ = lax.scan(tick, carry0, cols)

        loss = lax.psum(num_acc, AXES + (PP_AXIS,)) * inv_den
        gacc = pol.cast_grads(gacc)
        grads = {
            k: (lax.psum(g, AXES + (PP_AXIS,)) if k in SHARED_LEAVES
                else jax.tree.map(lambda a: lax.psum(a, AXES), g))
            for k, g in gacc.items()
        }
        grads = pol.upcast_grads(grads)
        new_params, new_opt = adam_update(params, opt_state, grads, lr=lr)
        out = ()
        if guard or health:
            # Both flags key off the same PartitionSpec-driven
            # reductions (obs.health, ISSUE 5): the stacked-block
            # leaves are stage-resident over pp (and Megatron-sharded
            # over tp), so their counts/squared sums reduce over
            # exactly the axes their PartitionSpec names; the
            # pp-replicated shared leaves are already fully reduced.
            # Python-level flags: health=False, guard=False compiles
            # the exact pre-change program.
            from ..models.partition import pipeline_param_specs
            from ..obs import health as hlt

            pspecs = pipeline_param_specs(
                config.spec, part.pp, config.tensor_parallel
            )
        if guard:
            # ISSUE 6 step guard: identity instead of the Adam update
            # when ANY stage's gradients went non-finite (the count is
            # globally reduced, so every pp/tp position selects the
            # same branch); the int32 skip flag rides as LAST output.
            from ..resilience.guard import apply_guard

            new_params, new_opt, skipped = apply_guard(
                hlt.nonfinite_count(grads, pspecs),
                params, opt_state, new_params, new_opt,
            )
            out = (skipped,)
        if health:
            h = hlt.health_signals(grads, params, new_params, pspecs)
            out = (h,) + out
        return (new_params, new_opt, loss) + out

    return step


def make_pipeline_eval_body(config, part, platform):
    """Forward-only pipeline eval, already inside ``shard_map``:
    ``(params, tokens, targets, weights) -> (num, den)`` — weighted
    top-1 hit sums (``lm_correct_sums``'s accumulator contract). The
    whole eval set flows through as ONE microbatch: ``pp - 1`` hops move
    it stage to stage (each device applies its local layers every hop —
    only the position that has the real activation computes on data),
    the last stage scores. ``num``/``den`` psum exactly like the 2-D
    trainer's eval (test data is dp-replicated, so both inflate dp-fold
    and the accuracy ratio is exact)."""
    pp = part.pp
    _, blocks_fwd = make_stage_fn(config, platform)
    act_dtype = config.dtype() or jnp.float32
    fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

    def sums(params, tokens, targets, weights):
        s_idx = lax.axis_index(PP_AXIS)
        first = s_idx == 0
        last = s_idx == pp - 1
        p = params
        if config.dtype() is not None:
            p = jax.tree.map(lambda a: a.astype(config.dtype()), dict(p))
        positions = jnp.arange(tokens.shape[1])
        emb = p["embed"][tokens].astype(act_dtype)
        h = jnp.where(first, emb, jnp.zeros_like(emb))
        for _ in range(pp - 1):
            h = lax.ppermute(
                blocks_fwd(p["blocks"], h, positions), PP_AXIS, fwd_perm
            )
        h = blocks_fwd(p["blocks"], h, positions)
        hl = transformer._layernorm(h, p["lnf_g"], p["lnf_b"])
        logits = (hl @ p["head"]).astype(jnp.float32)
        hits = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
        w = weights.astype(jnp.float32)
        num = jnp.where(last, jnp.sum(hits * w), 0.0)
        return (lax.psum(num, AXES + (PP_AXIS,)),
                lax.psum(jnp.sum(w), AXES))

    return sums
