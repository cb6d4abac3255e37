"""Sequence/context-parallel training: the long-context strategy.

The reference's strategy matrix stops at data parallelism and parameter
sharding (SURVEY.md §2.3; it has no sequence axis anywhere —
mnist_sync/model/model.py:18-19). This strategy goes beyond that matrix:
it trains the decoder-only LM (``models.transformer``) over a 2-D
``[data_parallel, num_workers]`` mesh — the batch shards over dp rows and
the SEQUENCE dimension over sp columns, so context length scales past one
chip's HBM. Each device holds ``B/dp`` sequences x ``T/sp`` positions;
``data_parallel=1`` (the default) is pure sequence parallelism.

Scheme selection (``SeqConfig.scheme``):

- ``ring``    — ring attention: K/V blocks rotate via ``lax.ppermute``
  over ICI neighbour links; exact streaming-softmax attention with
  O(T/W * T/W) score memory per device (``ring.ring_attention_shard``).
- ``ulysses`` — two ``lax.all_to_all``s re-partition sequence-sharded
  activations to head-sharded and back; needs ``num_heads % W == 0``.
- ``full``    — no cross-shard attention (W=1 only): the single-device
  oracle the parity tests compare against.

Everything outside ``attn_fn`` is position-local, so the ONLY cross-shard
communication per step is inside attention plus one gradient ``psum``
(inserted automatically by ``shard_map``'s transpose for the replicated
param cotangents) and the scalar loss normalization ``psum``.

``SeqConfig.zero1`` composes the beyond-parity stories: (data x
sequence) parallelism × ZeRO-1. The update switches to the CNN sharded
path's schedule (strategies/sync.py ``_sharded_step_body``) over the
COMBINED mesh axes — local (unreduced) grads, one fused ``psum_scatter``
of the flat gradient that both sums the dp/sp partial gradients and
lands each of the dp*sp devices its owned chunk, Adam there (m/v live
ONLY on the owner: the 2x-optimizer-state memory saving), ``all_gather``
of the updated params. Collective bytes per step equal the replicated
path's all-reduce (RS+AG is how XLA lowers a ring all-reduce anyway);
what's saved is optimizer memory and update compute, both /(dp*sp).
Checkpoints store m/v in params-shaped form, so a run can resume across
zero1 on/off AND across any (dp, sp) topology (elastic, like the CNN
trainers).

``zero1 x tensor_parallel`` composes both onto the full 3-D mesh via
the HYBRID sharded optimizer (``_zero1_tp_step_body``): the Megatron
column/row-sharded block weights keep tp-local Adam state (already
sharded tp-fold with the weights), while the tp-REPLICATED subtree —
embed, head, every LayerNorm, b2: the leaves that would otherwise hold
dp*sp*tp redundant Adam copies — is flattened, reduce-scattered and
updated shard-resident over the combined (dp, sp) axes, then
all-gathered (cross-replica weight-update sharding, Xu et al.
arXiv:2004.13336, on the dp x sp x tp recipe of arXiv:2204.06514).
Gradient correctness in local-grads mode is owned by the explicit
Megatron f/g ``custom_vjp`` pair (parallel/collectives.py
``tp_allreduce``/``tp_promote``) threaded through ``apply_lm`` — no
gradient ever rides a bare psum transpose.

Same training machinery as the other strategies: device-resident
``eval_spans`` span programs (AOT-compiled), ``StepTimer`` percentiles,
``--target-accuracy`` early stop, deterministic seeded init.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Literal

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..data.lm import LMDataset
from ..models import transformer
from ..obs import health as hlt
from ..obs.trace import NULL_TRACER
from ..models.transformer import LMSpec
from ..ops import adam_init, adam_update
from ..ops.optimizers import AdamState
from ..parallel import collectives as coll
from ..parallel import multihost, ring
from ..parallel.mesh import (
    DP_AXIS,
    SP_AXIS,
    TP_AXIS,
    donation_for,
    make_mesh_2d,
    make_mesh_3d,
    make_mesh_4d,
)
from .sync import ShardedAdam, _adam_flat
from ..train.trainer import (
    check_preempt,
    checkpoint_file,
    eval_spans,
    force,
    guarded,
    hit_target,
    resume_plan,
    save_crossed,
    steps_scan,
    try_resume,
)
from ..utils.checkpoint import save_checkpoint
from ..utils.metrics import StepStats, StepTimer, trace

Scheme = Literal["ring", "ulysses", "full"]

# The 2-D mesh: batch over rows (dp), sequence over columns (sp). A
# data_parallel=1 config is the [1, W] degenerate case — one program
# family covers both. Collectives that need the GLOBAL reduction (loss
# sums, the ZeRO-1 scatter/gather) run over the combined axes, lex order
# (dp-major) matching ``NamedSharding(P(AXES))`` chunk order.
AXES = (DP_AXIS, SP_AXIS)


@dataclasses.dataclass(frozen=True)
class SeqConfig:
    epochs: int = 1
    batch_size: int = 8  # sequences per GLOBAL batch (shards over dp rows)
    learning_rate: float = 1e-3
    eval_every: int = 10  # batches between test-set evals (0 = end only)
    seed: int = 0
    num_workers: int = 1  # sequence-parallel degree (sp mesh axis size)
    # Data-parallel degree (dp mesh axis): the global batch shards over
    # dp rows; total devices = data_parallel * num_workers.
    data_parallel: int = 1
    # Tensor-parallel degree (tp mesh axis, Megatron sharding): each
    # block's wq/wk/wv/w1 shard column-wise (each device owns H/tp heads
    # and d_ff/tp hidden units) and wo/w2 row-wise; the attention and
    # MLP outputs are completed by ONE psum over tp each — the only
    # tensor-parallel collectives. The residual stream stays full-width
    # everywhere, so tp composes orthogonally with sequence parallelism
    # (the ring runs per local head subset) and data parallelism:
    # total devices = data_parallel * num_workers * tensor_parallel on
    # a 3-D [dp, sp, tp] mesh (tp minor — its psums are the highest-
    # frequency collective, so they ride neighbouring ICI links).
    tensor_parallel: int = 1
    scheme: Scheme = "ring"
    compute_dtype: str | None = None  # None = fp32; "bfloat16" = MXU path
    # Precision policy (ddl_tpu.precision): "fp32" (today's programs,
    # byte-identical) or "bf16" (bf16 activations AND gradient
    # reductions, fp32 master weights + Adam moments — arXiv
    # 2204.06514's split). None defers to the legacy compute_dtype
    # thread: a bare compute_dtype="bfloat16" keeps compiling its
    # pre-policy program (bf16 compute, fp32 reductions).
    precision: str | None = None
    target_accuracy: float | None = None
    # ZeRO-1 over the combined (dp, sp) axes: reduce-scatter grads, Adam
    # on each device's flat chunk (m/v owner-resident), all_gather
    # params. Composes with tensor_parallel > 1 as the HYBRID sharded
    # optimizer (``_zero1_tp_step_body``): tp-sharded weights keep
    # tp-local Adam state while the tp-REPLICATED subtree (embed/head/
    # LNs/b2) flattens and shards over dp x sp — its per-device
    # optimizer-state and gradient-peak bytes drop /(dp*sp), and its
    # full grad psum becomes reduce-scatter + all-gather.
    zero1: bool = False
    # Local attention kernel: "xla" = the plain einsum softmax
    # (materializes [B, H, T, T] scores); "flash" = the Pallas flash
    # kernel on TPU / its pure-JAX reference off-TPU (ops/attention.py).
    # Available for schemes full and ulysses; the ring keeps its own
    # blockwise streaming softmax.
    attn_impl: Literal["xla", "flash"] = "xla"
    # Rematerialize each transformer block in the backward pass
    # (jax.checkpoint): saved activation state per block drops from the
    # attention residuals — the ring's O(T^2/P)-per-device sweep tiles —
    # to the block input (O(T/P * d_model)), for ~1/3 extra FLOPs (one
    # recomputed forward per block, the ring's ppermute chain included).
    # The long-context memory lever (scaling-book recipe); measured by
    # tests/test_lm.py and benchmarks/lm_longseq.py --remat.
    remat: bool = False
    # Position-to-device layout for scheme="ring": "contiguous" = block i
    # on device i (device P-1 then computes on EVERY causal ring step —
    # the last-device hot spot); "zigzag" = the two-ended layout (device i
    # holds chunks i and 2P-1-i of 2P), which halves the causal critical
    # path (ring.causal_work_profile). Data movement is a staging-time
    # gather (ring.zigzag_permutation); RoPE gets the matching absolute
    # positions, so training is numerically the same computation.
    seq_layout: Literal["contiguous", "zigzag"] = "contiguous"
    # Pipeline parallelism (ddl_tpu.pipeline): the LAYER STACK splits
    # into pipeline_parallel contiguous stages over the pp mesh axis
    # (minor — stage-hop ppermutes ride neighbouring ICI links); the
    # global batch splits into `microbatches` that stream through the
    # stages per `pipeline_schedule` (gpipe = flush; 1f1b = steady-state
    # interleave with min(pp, M) instead of M in-flight activations per
    # stage). Composes with data_parallel and tensor_parallel on the
    # 4-D [dp, 1, tp, pp] mesh; sequence parallelism and zero1 are
    # rejected with pipeline_parallel > 1 (validate_topology; README
    # composition matrix).
    pipeline_parallel: int = 1
    microbatches: int = 1
    pipeline_schedule: Literal["gpipe", "1f1b"] = "gpipe"
    spec: LMSpec = LMSpec()

    def policy(self):
        """The resolved precision policy (``ddl_tpu.precision.resolve``
        over this config's precision/compute_dtype pair); every step
        body brackets its gradient reduction with the policy's
        cast/upcast hooks — Python-level no-ops off-path."""
        from .. import precision as _precision

        return _precision.resolve(self.precision, self.compute_dtype)

    def dtype(self):
        return self.policy().compute_dtype

    def validate_topology(self, seq_len: int | None = None,
                          platform: str | None = None) -> None:
        """Fail-fast topology validation (one place, unit-tested):
        SeqTrainer calls this before ANY device work, so a
        misconfiguration is a clean ValueError with the fix, never a
        shape error deep inside shard_map. Benchmarks that measure the
        step machinery directly (pipeline_bubble's microbatches=1
        zero-pipelining anchor) construct configs without it.

        ``seq_len`` and ``platform`` (the dataset's sequence length and
        the mesh's device platform, where the caller knows them) gate
        the one shape rule a TPU kernel imposes: the flash kernel takes
        whole 128-token blocks only."""
        if (self.attn_impl == "flash" and platform == "tpu"
                and seq_len is not None):
            from ..ops.attention import FLASH_BLOCK

            if seq_len % FLASH_BLOCK:
                padded = -(-seq_len // FLASH_BLOCK) * FLASH_BLOCK
                raise ValueError(
                    f"attn_impl='flash' on TPU needs seq_len to be a "
                    f"multiple of {FLASH_BLOCK} (the kernel's block), got "
                    f"{seq_len}; use attn_impl='xla' for this length or "
                    f"a sequence of {padded}"
                )
        pp = self.pipeline_parallel
        m = self.microbatches
        if pp < 1:
            raise ValueError(f"pipeline_parallel must be >= 1, got {pp}")
        if m < 1:
            raise ValueError(f"microbatches must be >= 1, got {m}")
        if m > 1 and pp == 1:
            raise ValueError(
                f"microbatches ({m}) > 1 requires pipeline_parallel > 1 "
                "(microbatching exists to fill the pipeline; without "
                "stages it only re-associates the batch)"
            )
        if pp == 1:
            return
        if self.spec.num_layers % pp:
            raise ValueError(
                f"pipeline_parallel ({pp}) must divide num_layers "
                f"({self.spec.num_layers}) — stages are contiguous "
                "equal layer blocks"
            )
        if m < 2:
            raise ValueError(
                f"pipeline_parallel ({pp}) > 1 requires microbatches > 1 "
                f"— one microbatch leaves (pp-1)/pp = {pp - 1}/{pp} of "
                "every step idle (the GPipe bubble); pass "
                "--microbatches >= 2"
            )
        if self.batch_size % (self.data_parallel * m):
            raise ValueError(
                f"microbatches ({m}) x data_parallel "
                f"({self.data_parallel}) must divide the global batch "
                f"({self.batch_size}) — each dp row streams equal "
                "microbatches through the stages"
            )
        if self.num_workers != 1 or self.scheme != "full":
            raise ValueError(
                "pipeline_parallel composes with data/tensor parallelism "
                "only: use num_workers=1 and scheme='full' (sequence x "
                "pipeline is rejected — README composition matrix)"
            )
        if self.zero1:
            raise ValueError(
                "zero1 x pipeline_parallel is not supported: the "
                "pipeline Adam path keeps stage-local optimizer state "
                "(already sharded pp-fold with the layers); see the "
                "README composition matrix"
            )
        from ..pipeline.schedule import SCHEDULES

        if self.pipeline_schedule not in SCHEDULES:
            raise ValueError(
                f"unknown pipeline_schedule {self.pipeline_schedule!r} "
                f"(choices: {', '.join(SCHEDULES)})"
            )


@dataclasses.dataclass
class LMResult:
    params: dict
    final_accuracy: float  # weighted next-token accuracy on the test set
    final_loss: float
    wall_time_s: float
    train_time_s: float  # span dispatch only; evals and compilation excluded
    history: list[tuple[int, int, float]]  # (epoch, batch, accuracy)
    tokens_per_sec: float  # scored + unscored tokens (B * T) / train_time_s
    compile_time_s: float = 0.0
    step_stats: StepStats | None = None
    resumed_from_step: int = 0  # global batch restored from a checkpoint
    preempted: bool = False  # stopped early by should_stop (e.g. SIGTERM)
    skipped_steps: int = 0  # updates skipped by the non-finite guard
    rollbacks: int = 0  # guard escalations to the last good checkpoint


def _vary_axes(config: SeqConfig) -> tuple[str, ...]:
    """Every mesh axis the ring's q/k/v inputs vary over: dp/sp always
    (data), plus tp when the block weights are tensor-sharded (q/k/v
    then carry the tp-sharded head subset)."""
    return AXES + (TP_AXIS,) if config.tensor_parallel > 1 else AXES


def _row_reduce(config: SeqConfig):
    """Megatron's ``g`` for apply_lm's row-sharded matmul outputs:
    all-reduce forward, identity backward (``collectives.tp_allreduce``
    — an explicit custom_vjp, so the gradient never depends on which
    psum-transpose rule this JAX generation ships). None when tp=1 —
    no collective inserted."""
    if config.tensor_parallel == 1:
        return None
    return coll.tp_allreduce(TP_AXIS)


def _col_promote(config: SeqConfig):
    """Megatron's ``f`` — ``_row_reduce``'s conjugate: identity forward,
    all-reduce backward where the tp-replicated residual stream enters
    the column-sharded matmuls, so the replicated subtree (LNs, embed)
    receives FULL gradients even in the local-grads step bodies. None
    when tp=1."""
    if config.tensor_parallel == 1:
        return None
    return coll.tp_promote(TP_AXIS)


def _attn_for(config: SeqConfig, platform: str | None = None):
    """The per-shard attention closure for this config — always causal
    (decoder LM). ``full`` is the W=1 oracle; ring/ulysses derive their
    absolute positions from ``lax.axis_index`` inside the shard.
    ``attn_impl="flash"`` swaps the full-sequence kernel for the Pallas
    flash kernel (ops/attention.py) where the shapes allow it;
    ``platform`` is the mesh's device platform, forwarded so kernel
    selection follows where the program actually runs, not the default
    backend (round-4 advisor — a trainer jitting onto a non-default
    backend would otherwise pick the wrong kernel)."""
    W = config.num_workers
    if config.attn_impl not in ("xla", "flash"):
        # Literal annotations don't validate at runtime — an unknown
        # kernel name must not silently run the einsum path (found by a
        # round-5 bench-harness simulation doing exactly that).
        raise ValueError(f"unknown attn_impl {config.attn_impl!r}")
    flash = config.attn_impl == "flash"
    if flash and config.scheme == "ring":
        raise ValueError(
            "attn_impl='flash' supports schemes full and ulysses; the "
            "ring's travelling-block softmax state cannot route through "
            "the bundled kernel (ops/attention.py module docstring)"
        )
    if config.scheme == "full":
        if W != 1:
            raise ValueError("scheme='full' cannot shard the sequence; "
                             "use ring or ulysses for num_workers > 1")
        if flash:
            from ..ops.attention import flash_attention_bthd

            return functools.partial(
                flash_attention_bthd, causal=True, platform=platform
            )
        return functools.partial(ring.full_attention, causal=True)
    if config.scheme == "ring":
        return functools.partial(
            ring.ring_attention_shard, axis_name=SP_AXIS, axis_size=W,
            causal=True, vary_axes=_vary_axes(config),
            layout=config.seq_layout,
        )
    if config.scheme == "ulysses":
        local = None
        if flash:
            from ..ops.attention import flash_attention_bthd

            local = functools.partial(
                flash_attention_bthd, causal=True, platform=platform
            )
        return functools.partial(
            ring.ulysses_attention_shard, axis_name=SP_AXIS, axis_size=W,
            causal=True, local_attn=local,
        )
    raise ValueError(f"unknown scheme {config.scheme!r}")


def _shard_positions(config: SeqConfig, t_local: int) -> jax.Array:
    """This sp shard's absolute token positions ``[t_local]`` (traced —
    ``lax.axis_index`` based), per the config's layout. Feeds BOTH RoPE
    (transformer ``positions=``) and the ring's causal masking, so the
    two can never disagree about where a shard's tokens live."""
    i = lax.axis_index(SP_AXIS)
    if config.seq_layout == "zigzag":
        return ring.zigzag_positions(i, config.num_workers, t_local)
    return i * t_local + jnp.arange(t_local)


def _vary_all(x):
    """Widen ``x``'s varying set to the full 2-D mesh (no-op under
    ``check_vma=False``, where values carry no vma type)."""
    try:
        vma = jax.typeof(x).vma
    except AttributeError:
        return x
    missing = tuple(a for a in AXES if a not in vma)
    return lax.pcast(x, axis_name=missing, to="varying") if missing else x


def _shard_sums(config: SeqConfig, fn, platform: str | None = None):
    """Per-shard ``(global_num, global_den)`` for an accumulator-form
    metric ``fn`` (``lm_loss_sums`` / ``lm_correct_sums``): local sums
    over this shard's ``B/dp`` sequences x ``T/sp`` positions, ``psum``med
    over BOTH mesh axes. Global-mean-of-sums, NOT mean-of-shard-means —
    the loss mask is concentrated in the sequence's second half, so sp
    shards hold unequal scored-token counts (data.lm module docstring)."""
    attn = _attn_for(config, platform)

    def sums(params, tokens, targets, weights):
        t_local = tokens.shape[1]
        num, den = fn(
            params, tokens, targets, weights, config.spec, attn_fn=attn,
            positions=_shard_positions(config, t_local),
            compute_dtype=config.dtype(), remat=config.remat,
            row_reduce=_row_reduce(config), col_promote=_col_promote(config),
        )
        # Global sums over BOTH axes: sp shards hold different positions,
        # dp rows different sequences. (Eval data replicated over dp
        # inflates num and den equally — the ratio is exact.) _vary_all
        # widens each sum's varying set to both axes first — a partially
        # invariant sum (eval: dp-invariant) is otherwise rejected by the
        # combined-axes psum's vma check.
        return lax.psum(_vary_all(num), AXES), lax.psum(_vary_all(den), AXES)

    return sums


def _param_specs(config: SeqConfig):
    """The Megatron column/row (or replicated, tp=1) PartitionSpec tree
    for this config's params — ONE definition shared with the serving
    mesh (``models.partition.lm_param_specs``), so a checkpoint trained
    here re-shards onto ``ddl_tpu.serve`` without conversion."""
    from ..models.partition import lm_param_specs

    return lm_param_specs(config.spec, config.tensor_parallel)


class _FlatPlan:
    """Static flatten/unflatten plan for the (nested) LM param tree —
    ``jax.flatten_util.ravel_pytree`` with the unravel closure captured
    once from a template, the nested-pytree analogue of
    ``collectives.FlatSpec`` (which is keyed by flat variable names)."""

    def __init__(self, template):
        flat, self.unflatten = jax.flatten_util.ravel_pytree(template)
        self.total = int(flat.size)

    @staticmethod
    def flatten(tree) -> jax.Array:
        return jax.flatten_util.ravel_pytree(tree)[0]


def _zero1_step_body(config: SeqConfig, plan: _FlatPlan,
                     platform: str | None = None, health: bool = False,
                     guard: bool = False):
    """One ZeRO-1 train step inside ``shard_map`` (``check_vma=False``,
    like the CNN sharded path): grads here are LOCAL — each shard
    differentiates its own scored-token sum over the GLOBAL denominator
    (the psum'd weight total carries no param dependence) — so the fused
    ``psum_scatter`` performs the one and only cross-shard reduction.
    On the 2-D mesh the scatter runs over the COMBINED (dp, sp) axes:
    one collective both sums the dp/sp partial gradients and lands each
    of the dp*sp devices its owned chunk.

    Under ``precision="bf16"`` the policy casts the flat gradient to
    bf16 BEFORE the scatter (halved collective bytes) and upcasts the
    owned chunk at the Adam boundary (fp32 m/v/master — the arXiv
    2204.06514 split); Python-level no-ops off-path."""
    attn = _attn_for(config, platform)
    pol = config.policy()
    n_dev = config.data_parallel * config.num_workers
    chunk = coll.chunk_size(plan.total, n_dev)

    def step(params, opt: ShardedAdam, tokens, targets, weights):
        local_loss = _local_loss_fn(config, attn, tokens, targets, weights)
        l_local, grads = jax.value_and_grad(local_loss)(params)
        loss = lax.psum(l_local, AXES)  # global weighted mean, replicated
        g_own = coll.reduce_scatter_flat(
            plan.flatten(pol.cast_grads(grads)), n_dev, AXES, mean=False,
            chunk=chunk,
        )
        g_own = pol.upcast_grads(g_own)
        my_chunk = lax.axis_index(DP_AXIS) * config.num_workers \
            + lax.axis_index(SP_AXIS)  # lex order, = psum_scatter's split
        p_own = lax.dynamic_slice(
            coll.pad_to(plan.flatten(params), chunk * n_dev),
            (my_chunk * chunk,), (chunk,),
        )
        old_opt = opt
        p_new, opt = _adam_flat(p_own, opt, g_own, lr=config.learning_rate)
        full = lax.all_gather(p_new, AXES, tiled=True)[: plan.total]
        new_tree = plan.unflatten(full)
        out = ()
        if guard:
            # The non-finite count over the flat chunks (disjoint over
            # dp x sp — one psum is the global, replicated answer), so
            # every device selects the SAME branch.
            from ..resilience.guard import apply_guard

            _, nf = hlt.flat_grad_sq_nonfinite(g_own, AXES)
            new_tree, opt, skipped = apply_guard(
                nf, params, old_opt, new_tree, opt
            )
            out = (skipped,)
        if health:
            # Grad stats from the flat chunks (disjoint over dp x sp —
            # one psum is the global answer); param/update norms from
            # the full trees both sides of the APPLIED update, which
            # zero1 keeps replicated.
            sq, nf = hlt.flat_grad_sq_nonfinite(g_own, AXES)
            h = {"grad_norm": jnp.sqrt(sq), "nonfinite_grads": nf,
                 **hlt.norm_signals(params, new_tree, None)}
            out = ({k: h[k] for k in hlt.health_keys(params)},) + out
        return (new_tree, opt, loss) + out

    return step


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridAdam:
    """Optimizer state for the zero1 x tensor_parallel composition.

    Two placements in one state, mirroring how the weights themselves
    live on the 3-D mesh:

    - the REPLICATED subtree (embed/head/LayerNorms/b2 — every leaf
      whose weight is tp-replicated) flattens into ``m_flat``/``v_flat``
      chunks sharded ``P((dp, sp))``: ``rep_total/(dp*sp)`` elements
      resident per device, replicated over tp — the cross-replica
      weight-update sharding of Xu et al. (arXiv:2004.13336) applied to
      exactly the subtree that still had dp*sp redundant Adam copies;
    - the tp-SHARDED leaves (wq/wk/wv/wo/w1/b1/w2) keep params-shaped
      ``m_tp``/``v_tp`` lists placed like the weights (already sharded
      tp-fold): their optimizer state was never replicated over tp, and
      re-flattening it over (dp, sp) as well would buy /(dp*sp) at the
      cost of a second scatter/gather pair per step on the hot path.

    One shared ``step`` drives both parts' bias correction.
    """

    step: jax.Array  # int32 scalar, replicated
    m_flat: jax.Array  # [dp*sp*chunk] over P((dp, sp)), tp-replicated
    v_flat: jax.Array
    m_tp: list  # tp-sharded leaves, params-shaped (specs = weight specs)
    v_tp: list


class _HybridPlan:
    """Leaf-aligned split of the LM param tree for zero1 x tp: the
    tp-SHARDED leaves (PartitionSpec mentions TP_AXIS) keep their tree
    shapes; the REPLICATED remainder gets a static flatten/unflatten
    plan (the ``_FlatPlan`` analogue over a leaf subset). Built from
    the HOST-side init template, so constructing it moves no device
    data."""

    def __init__(self, template, pspecs):
        leaves, self.treedef = jax.tree.flatten(template)
        spec_leaves = jax.tree.flatten(
            pspecs, is_leaf=lambda s: isinstance(s, P)
        )[0]
        assert len(spec_leaves) == len(leaves), "spec/param tree mismatch"
        self.tp_mask = tuple(s != P() for s in spec_leaves)
        self.tp_specs = [s for s in spec_leaves if s != P()]
        rep_template = [
            np.zeros(np.shape(l), np.float32)
            for l, m in zip(leaves, self.tp_mask) if not m
        ]
        flat, self._unravel_rep = jax.flatten_util.ravel_pytree(rep_template)
        self.rep_total = int(flat.size)

    def split(self, tree) -> tuple[list, list]:
        """Tree -> (replicated leaves, tp-sharded leaves), flatten order."""
        leaves = jax.tree.leaves(tree)
        rep = [l for l, m in zip(leaves, self.tp_mask) if not m]
        tp = [l for l, m in zip(leaves, self.tp_mask) if m]
        return rep, tp

    def merge(self, rep: list, tp: list):
        """Inverse of :meth:`split`: interleave back into the full tree."""
        rep_it, tp_it = iter(rep), iter(tp)
        leaves = [next(tp_it) if m else next(rep_it) for m in self.tp_mask]
        return jax.tree.unflatten(self.treedef, leaves)

    @staticmethod
    def flatten_rep(rep: list) -> jax.Array:
        return jax.flatten_util.ravel_pytree(rep)[0]

    def unflatten_rep(self, flat) -> list:
        return self._unravel_rep(flat[: self.rep_total])


def _zero1_tp_step_body(config: SeqConfig, hplan: _HybridPlan,
                        platform: str | None = None, health: bool = False,
                        guard: bool = False):
    """One hybrid zero1 x tensor_parallel train step inside ``shard_map``
    (``check_vma=False``). Local grads come out of ``_local_loss_fn``
    dp/sp-partial and tp-complete (the f/g pair); then each subtree gets
    the reduction its placement wants:

    - REPLICATED subtree: ONE fused ``psum_scatter`` over the combined
      (dp, sp) axes both sums the partials and lands each of the dp*sp
      devices its owned flat chunk (tp peers compute identical chunks —
      the redundancy is free tp-replication of the result), Adam runs on
      the chunk (m/v owner-resident: optimizer memory /(dp*sp)), and one
      ``all_gather`` rebuilds the full subtree — reduce-scatter +
      all-gather REPLACES the replicated path's full psum of this
      subtree on the hot path;
    - tp-SHARDED leaves: one ``psum`` over (dp, sp) per leaf (their tp
      reduction doesn't exist — each device owns its shard outright),
      then the SAME TF1-Adam update the replicated path applies, on
      m/v that live sharded tp-fold with the weights.

    Under ``precision="bf16"`` BOTH subtrees' reductions move bf16
    bytes — the flat scatter and the per-leaf psums — and both upcast
    at their Adam boundary (ddl_tpu.precision); no-ops off-path.
    """
    attn = _attn_for(config, platform)
    pol = config.policy()
    n_dev = config.data_parallel * config.num_workers
    chunk = coll.chunk_size(hplan.rep_total, n_dev)

    def step(params, opt: HybridAdam, tokens, targets, weights):
        local_loss = _local_loss_fn(config, attn, tokens, targets, weights)
        l_local, grads = jax.value_and_grad(local_loss)(params)
        loss = lax.psum(l_local, AXES)  # global weighted mean, replicated
        g_rep, g_tp = hplan.split(pol.cast_grads(grads))
        p_rep, p_tp = hplan.split(params)

        # Replicated subtree: ZeRO-1 over the combined (dp, sp) axes.
        g_own = coll.reduce_scatter_flat(
            hplan.flatten_rep(g_rep), n_dev, AXES, mean=False, chunk=chunk
        )
        g_own = pol.upcast_grads(g_own)
        my_chunk = lax.axis_index(DP_AXIS) * config.num_workers \
            + lax.axis_index(SP_AXIS)  # lex order, = psum_scatter's split
        p_own = lax.dynamic_slice(
            coll.pad_to(hplan.flatten_rep(p_rep), chunk * n_dev),
            (my_chunk * chunk,), (chunk,),
        )
        flat = ShardedAdam(step=opt.step, m=opt.m_flat, v=opt.v_flat)
        p_new, flat = _adam_flat(p_own, flat, g_own, lr=config.learning_rate)
        rep_new = hplan.unflatten_rep(
            lax.all_gather(p_new, AXES, tiled=True)
        )

        # tp-sharded leaves: full (dp, sp) reduction, tp-local Adam with
        # the SHARED step counter (flat.step == opt.step + 1 already).
        g_tp = [pol.upcast_grads(lax.psum(g, AXES)) for g in g_tp]
        tp_new, tp_state = adam_update(
            p_tp, AdamState(step=opt.step, m=opt.m_tp, v=opt.v_tp), g_tp,
            lr=config.learning_rate,
        )
        new_opt = HybridAdam(step=flat.step, m_flat=flat.m, v_flat=flat.v,
                             m_tp=tp_state.m, v_tp=tp_state.v)
        new_tree = hplan.merge(rep_new, tp_new)

        def global_nonfinite():
            # Flat-chunk count over (dp, sp) + the tp leaves' count
            # (g_tp is already (dp, sp)-complete per shard, so their
            # non-finite counts reduce over tp only) — replicated.
            _, nf = hlt.flat_grad_sq_nonfinite(g_own, AXES)
            tp_nf = sum(
                (jnp.sum(~jnp.isfinite(g.astype(jnp.float32)))
                 .astype(jnp.int32) for g in g_tp),
                jnp.int32(0),
            )
            return nf + lax.psum(tp_nf, TP_AXIS)

        out = ()
        if guard:
            from ..resilience.guard import apply_guard

            new_tree, new_opt, skipped = apply_guard(
                global_nonfinite(), params, opt, new_tree, new_opt
            )
            out = (skipped,)
        if health:
            # Replicated subtree: flat-chunk stats over (dp, sp). tp
            # leaves reduce their squared sums over tp. Param/update
            # norms take the trainer's spec tree, which names exactly
            # that tp sharding; the update is the APPLIED one.
            sq, _ = hlt.flat_grad_sq_nonfinite(g_own, AXES)
            tp_sq = sum(
                (jnp.sum(jnp.square(g.astype(jnp.float32))) for g in g_tp),
                jnp.float32(0.0),
            )
            sq = sq + lax.psum(tp_sq, TP_AXIS)
            h = {"grad_norm": jnp.sqrt(sq),
                 "nonfinite_grads": global_nonfinite(),
                 **hlt.norm_signals(params, new_tree, _param_specs(config))}
            out = ({k: h[k] for k in hlt.health_keys(params)},) + out
        return (new_tree, new_opt, loss) + out

    return step


def _local_loss_fn(config: SeqConfig, attn, tokens, targets, weights):
    """The per-device loss every train-step body differentiates: this
    shard's scored-token CE sum over the GLOBAL (psum'd) weight total.
    The division's psum carries no parameter dependence, so the returned
    gradients are LOCAL — dp/sp-partial sums awaiting ONE explicit
    reduction chosen by the caller (full ``psum`` for the replicated
    update, fused ``psum_scatter`` for ZeRO-1) — and tp-COMPLETE (the
    Megatron f/g custom-vjp pair inside apply_lm owns every
    tensor-parallel reduction in both directions). No gradient ever
    rides a bare psum transpose, whose rule differs across JAX
    generations."""
    t_local = tokens.shape[1]
    pos = _shard_positions(config, t_local)

    def local_loss(p):
        num, den = transformer.lm_loss_sums(
            p, tokens, targets, weights, config.spec, attn_fn=attn,
            positions=pos, compute_dtype=config.dtype(),
            remat=config.remat, row_reduce=_row_reduce(config),
            col_promote=_col_promote(config),
        )
        return num / lax.psum(den, AXES)

    return local_loss


def _step_body(config: SeqConfig, platform: str | None = None,
               health: bool = False, guard: bool = False):
    """One train step, already inside ``shard_map`` (``check_vma=False``):
    local grads (see ``_local_loss_fn``), ONE explicit ``psum`` over the
    (dp, sp) axes — full gradients for replicated leaves, per-shard-full
    gradients for tp-sharded leaves (their dp/sp partials are
    tp-shard-local already) — then the TF1-Adam update on state that
    mirrors the param placement. The pattern is pinned against the
    single-device oracle by tests/test_lm.py.

    ``health=True`` appends the in-graph health dict (``obs.health``,
    computed on the FULLY-REDUCED grads — tp-sharded leaves' squared
    sums psum over tp per the param specs) as a fourth output; the flag
    is a Python-level branch, so ``health=False`` compiles the exact
    pre-observability program.

    Under ``precision="bf16"`` the policy's cast/upcast hooks bracket
    the psum — the wire moves bf16 gradient bytes, the optimizer sees
    fp32 (ddl_tpu.precision); both hooks are Python-level no-ops for
    fp32/legacy configs, which compile the exact pre-policy program."""
    attn = _attn_for(config, platform)
    pol = config.policy()

    def step(params, opt_state, tokens, targets, weights):
        local_loss = _local_loss_fn(config, attn, tokens, targets, weights)
        l_local, grads = jax.value_and_grad(local_loss)(params)
        loss = lax.psum(l_local, AXES)  # global weighted mean, replicated
        grads = pol.cast_grads(grads)
        grads = jax.tree.map(lambda g: lax.psum(g, AXES), grads)
        grads = pol.upcast_grads(grads)
        new_params, new_opt = adam_update(
            params, opt_state, grads, lr=config.learning_rate
        )
        out = ()
        if guard:
            from ..resilience.guard import apply_guard

            new_params, new_opt, skipped = apply_guard(
                hlt.nonfinite_count(grads, _param_specs(config)),
                params, opt_state, new_params, new_opt,
            )
            out = (skipped,)
        if health:
            h = hlt.health_signals(
                grads, params, new_params, _param_specs(config)
            )
            out = (h,) + out
        return (new_params, new_opt, loss) + out

    return step


class SeqTrainer:
    """LM trainer over the 2-D ``[data_parallel, num_workers]`` mesh.

    Data placement: token/target/weight batches ``[nb, B, T]`` staged
    ``P(None, dp, sp)`` — each device holds its dp row's ``B/dp``
    sequences and its sp column's ``T/sp`` window of them; the test set
    is ``P(None, sp)`` (dp-replicated); params and optimizer state
    replicated (or ZeRO-1 chunks over the combined axes with
    ``zero1=True``)."""

    def __init__(self, config: SeqConfig, dataset: LMDataset):
        W = config.num_workers
        dp = config.data_parallel
        tp = config.tensor_parallel
        ppl = config.pipeline_parallel
        # Pipeline topology rules first (pp | num_layers, microbatch
        # divisibility, the rejected compositions) — one unit-tested
        # gate on SeqConfig, shared with the CLI.
        config.validate_topology(seq_len=dataset.seq_len,
                                 platform=jax.devices()[0].platform)
        if dataset.seq_len % max(W, 1):
            raise ValueError(
                f"seq_len {dataset.seq_len} not divisible by {W} workers"
            )
        if tp > 1:
            if config.spec.num_heads % tp:
                raise ValueError(
                    f"tensor_parallel needs num_heads "
                    f"({config.spec.num_heads}) divisible by tp ({tp})"
                )
            if config.spec.d_ff % tp:
                raise ValueError(
                    f"tensor_parallel needs d_ff ({config.spec.d_ff}) "
                    f"divisible by tp ({tp})"
                )
        local_heads = config.spec.num_heads // max(tp, 1)
        if config.scheme == "ulysses" and local_heads % max(W, 1):
            raise ValueError(
                f"ulysses needs per-device num_heads ({local_heads}) "
                f"divisible by num_workers ({W})"
            )
        # BOTH splits checked: JAX clamps out-of-range gather indices
        # instead of erroring, so test ids >= vocab would silently read
        # wrong embedding rows and skew eval (round-4 advisor).
        for name, toks in (("train", dataset.tokens),
                           ("test", dataset.test_tokens)):
            if toks.size and toks.max() >= config.spec.vocab:
                raise ValueError(
                    f"{name} vocab {toks.max() + 1} exceeds model "
                    f"vocab {config.spec.vocab}"
                )
        if config.batch_size % max(dp, 1):
            raise ValueError(
                f"batch_size {config.batch_size} not divisible by "
                f"data_parallel {dp} (the batch shards over dp rows)"
            )
        if dataset.num_train // config.batch_size == 0:
            raise ValueError(
                f"batch_size {config.batch_size} exceeds "
                f"{dataset.num_train} train sequences"
            )
        if config.seq_layout == "zigzag":
            if config.scheme != "ring":
                raise ValueError(
                    "seq_layout='zigzag' balances the RING's causal sweep; "
                    "full/ulysses reassemble the whole sequence locally and "
                    "assume contiguous order — use scheme='ring'"
                )
            if dataset.seq_len % (2 * W):
                raise ValueError(
                    f"seq_layout='zigzag' needs seq_len % (2 * num_workers)"
                    f" == 0, got {dataset.seq_len} % {2 * W}"
                )
        if dp < 1 or W < 1 or tp < 1:
            raise ValueError(
                f"data_parallel ({dp}), num_workers ({W}) and "
                f"tensor_parallel ({tp}) must be >= 1"
            )
        _attn_for(config)  # fail fast: unknown scheme / full-with-sharding
        self.config = config
        self.dataset = dataset
        # pp=1, tp=1 keeps the 2-D mesh (and therefore every pre-tp
        # program byte for byte); tp>1 adds the minor tp axis; pp>1 the
        # 4-D mesh with pp minor (stage hops on neighbouring ICI links).
        self.mesh = (
            make_mesh_4d(dp, W, tp, ppl) if ppl > 1
            else make_mesh_3d(dp, W, tp) if tp > 1
            else make_mesh_2d(dp, W)
        )
        from ..models import partition as partition_mod

        self._partition = partition_mod
        self._part = (
            partition_mod.stage_partition(config.spec, ppl)
            if ppl > 1 else None
        )
        self._pspecs = (
            partition_mod.pipeline_param_specs(config.spec, ppl, tp)
            if ppl > 1 else _param_specs(config)
        )
        # Optimizer placement mirrors the params (m/v are params-shaped);
        # a single P() keeps put_tree's broadcast form at tp=1.
        self._opt_specs = (
            AdamState(step=P(), m=self._pspecs, v=self._pspecs)
            if tp > 1 or ppl > 1 else P()
        )
        # Kernel selection (flash vs reference twin) follows where the
        # program actually runs, not the default backend (round-4 advisor).
        self._platform = self.mesh.devices.flat[0].platform
        # Zigzag: one staging-time gather re-orders the sequence dim so
        # contiguous sp sharding lands chunk pair (i, 2P-1-i) on device i;
        # _shard_positions hands RoPE/masking the matching absolute
        # positions. None = contiguous (identity).
        self._perm = (
            ring.zigzag_permutation(W, dataset.seq_len)
            if config.seq_layout == "zigzag" else None
        )
        # multihost.put_tree: plain device_put single-process; in a
        # multi-process world every controller materializes the same
        # deterministic init and the global Array is assembled from
        # process-local data (no cross-host transfer; tp-sharded leaves
        # slice their tp dim per process — multihost.put).
        host_init = transformer.init_lm_params(
            jax.random.PRNGKey(config.seed), config.spec
        )
        # Standard params-shaped template (shapes only) — the checkpoint
        # form every mode reads/writes, including pipeline runs whose
        # LIVE params are the stacked-blocks tree.
        self._host_like = jax.eval_shape(lambda: host_init)
        if ppl > 1:
            self.params = multihost.put_tree(
                self.mesh, self._pspecs,
                partition_mod.stack_blocks(
                    jax.tree.map(np.asarray, host_init)
                ),
            )
        else:
            self.params = multihost.put_tree(
                self.mesh, self._pspecs, host_init
            )
        # Flatten plans built from the HOST template (building them from
        # the placed tree would gather the tp shards just to read shapes).
        self._plan = _FlatPlan(host_init)
        self._hplan = (
            _HybridPlan(host_init, self._pspecs)
            if config.zero1 and tp > 1 else None
        )
        if self._hplan is not None:
            # Hybrid: flat (dp, sp)-sharded chunks for the replicated
            # subtree + params-shaped tp-sharded m/v for the tp leaves.
            n_dev = dp * W
            chunk = coll.chunk_size(self._hplan.rep_total, n_dev)
            z = np.zeros(n_dev * chunk, np.float32)
            _, tp_leaves = self._hplan.split(host_init)
            zs = [np.zeros(np.shape(l), np.float32) for l in tp_leaves]
            put_tp = lambda zeros: [
                multihost.put(self.mesh, s, z.copy())
                for s, z in zip(self._hplan.tp_specs, zeros)
            ]
            self.opt_state: Any = HybridAdam(
                step=multihost.put(self.mesh, P(), np.zeros((), np.int32)),
                m_flat=multihost.put(self.mesh, P(AXES), z),
                v_flat=multihost.put(self.mesh, P(AXES), z.copy()),
                m_tp=put_tp(zs),
                v_tp=put_tp(zs),
            )
        elif config.zero1:
            n_dev = dp * W
            chunk = coll.chunk_size(self._plan.total, n_dev)
            z = np.zeros(n_dev * chunk, np.float32)
            self.opt_state = ShardedAdam(
                step=multihost.put(self.mesh, P(), np.zeros((), np.int32)),
                m=multihost.put(self.mesh, P(AXES), z),
                v=multihost.put(self.mesh, P(AXES), z.copy()),
            )
        else:
            self.opt_state = multihost.put_tree(
                self.mesh, self._opt_specs, adam_init(self.params)
            )

    # -- compiled programs -------------------------------------------------

    def _seq_spec(self, ndim: int) -> P:
        """Test-set placement: sequence over sp, batch replicated over dp
        (test batches need not divide by dp; the psum'd num/den both
        inflate dp-fold so accuracies stay exact)."""
        return P(*([None] * (ndim - 1) + [SP_AXIS]))

    def span_program(self, k: int, health: bool = False,
                     guard: bool = False):
        """``(params, opt, xs, ys, ws, first) -> (params, opt, loss)``:
        ``k`` consecutive batches as ONE device-resident program
        (``steps_scan`` span, same structure as ``trainer.make_epoch_chunk``).
        Public: benchmarks time exactly this object (lm_bench/scaling —
        the product path by construction).

        ``health=True`` appends a dict of ``[k]``-stacked in-graph
        health signals (``obs.health``) as a fourth output — computed
        per step inside the scan, fetched by the caller in ONE batched
        device->host transfer, so the hot path never gains a per-step
        sync. ``guard=True`` (ISSUE 6) compiles the NaN-guarded step —
        a non-finite gradient applies identity in-graph
        (``resilience.guard``) — and appends the ``[k]``-stacked int32
        skip flags as the LAST output. Both flags are Python branches:
        ``health=False, guard=False`` builds the exact pre-change
        program."""
        seq = P(DP_AXIS, SP_AXIS)  # train batch [B, T]: B over dp, T over sp
        hspec = hlt.health_out_specs(self._host_like) if health else None
        extra = (((hspec,) if health else ())
                 + ((P(),) if guard else ()))  # skipped flag: replicated
        # EVERY step body runs check_vma=False (local-grads mode): each
        # body computes unreduced dp/sp gradients and applies its own
        # explicit reduction (psum / psum_scatter); a replication checker
        # would auto-psum the replicated-param cotangents and the
        # explicit reduction would then double-count.
        if self.config.pipeline_parallel > 1:
            # Pipeline step: the schedule-tick scan over the pp axis
            # (microbatch split, manual per-microbatch backward, Adam on
            # pp/tp-placed state — pipeline.step); in/out specs mirror
            # this trainer's param/opt placement exactly.
            from ..pipeline.trainer import pipeline_shard_step

            shard_step = pipeline_shard_step(
                self.config, self.mesh, self._platform, health=health,
                guard=guard,
            )
        elif self._hplan is not None:
            opt_spec = HybridAdam(
                step=P(), m_flat=P(AXES), v_flat=P(AXES),
                m_tp=list(self._hplan.tp_specs),
                v_tp=list(self._hplan.tp_specs),
            )
            shard_step = jax.shard_map(
                _zero1_tp_step_body(self.config, self._hplan,
                                    self._platform, health=health,
                                    guard=guard),
                mesh=self.mesh,
                in_specs=(self._pspecs, opt_spec, seq, seq, seq),
                out_specs=(self._pspecs, opt_spec, P()) + extra,
                check_vma=False,
            )
        elif self.config.zero1:
            opt_spec = ShardedAdam(step=P(), m=P(AXES), v=P(AXES))
            shard_step = jax.shard_map(
                _zero1_step_body(self.config, self._plan, self._platform,
                                 health=health, guard=guard),
                mesh=self.mesh,
                in_specs=(P(), opt_spec, seq, seq, seq),
                out_specs=(P(), opt_spec, P()) + extra,
                check_vma=False,
            )
        else:
            shard_step = jax.shard_map(
                _step_body(self.config, self._platform, health=health,
                           guard=guard),
                mesh=self.mesh,
                in_specs=(self._pspecs, self._opt_specs, seq, seq, seq),
                out_specs=(self._pspecs, self._opt_specs, P()) + extra,
                check_vma=False,
            )

        def run(params, opt_state, xs, ys, ws, first):
            def body(carry, i):
                p, o = carry
                out = shard_step(p, o, xs[i], ys[i], ws[i])
                return (out[0], out[1]), tuple(out[2:])

            (params, opt_state), out = steps_scan(
                body, (params, opt_state), first + jnp.arange(k), k
            )
            # out = (losses[, healths][, skipped]), each [k]-stacked;
            # report the span's LAST loss, the stacked health dict and
            # the full stacked skip flags.
            res = (params, opt_state, out[0][-1])
            if health:
                res = res + (out[1],)
            if guard:
                res = res + (out[-1],)
            return res

        # Donate params + optimizer state (halved peak HBM, like every
        # other trainer's step); donation_for gates off the multi-device
        # CPU mesh where donated replicated args deadlock the in-process
        # AllReduce (mesh.py).
        return jax.jit(run, donate_argnums=donation_for(self.mesh, 0, 1))

    def _eval_fn(self):
        if self.config.pipeline_parallel > 1:
            # Forward-only pipeline eval (one microbatch, pp-1 stage
            # hops, last stage scores — pipeline.step); same hit-sums
            # contract and dp-replicated test placement as below.
            from ..pipeline.trainer import pipeline_shard_eval

            sums = pipeline_shard_eval(
                self.config, self.mesh, self._platform, P(None, SP_AXIS)
            )
        else:
            sums = jax.shard_map(
                _shard_sums(self.config, transformer.lm_correct_sums,
                            self._platform),
                mesh=self.mesh,
                in_specs=(self._pspecs, P(None, SP_AXIS), P(None, SP_AXIS),
                          P(None, SP_AXIS)),
                out_specs=(P(), P()),
                # No grads here, but the ring's causal lax.cond defeats
                # replication checkers that lack a cond rule (pre-vma JAX);
                # the trailing psums make the outputs replicated by
                # construction either way.
                check_vma=False,
            )

        def acc(params, tokens, targets, weights):
            num, den = sums(params, tokens, targets, weights)
            return num / den

        return jax.jit(acc)

    def _permuted(self, arr: np.ndarray) -> np.ndarray:
        """Apply the layout's sequence permutation (identity when
        contiguous) — tokens/targets/weights all move together, so the
        loss mask follows its tokens."""
        return arr if self._perm is None else arr[:, self._perm]

    def stage_batches(self, arr: np.ndarray, batches: int, bs: int) -> jax.Array:
        """Stage ``batches`` x ``bs`` rows of ``arr`` onto the mesh as
        the span programs' ``[nb, B, T]`` input placement. Public: the
        benchmarks stage through this so they feed ``span_program``
        exactly what the trainer does."""
        shaped = self._permuted(arr[: batches * bs]).reshape(
            batches, bs, arr.shape[1]
        )
        return multihost.put(self.mesh, P(None, DP_AXIS, SP_AXIS), shaped)

    # -- checkpoint form (elastic: params-shaped m/v in BOTH modes) --------

    def _opt_like(self):
        """Host-shaped checkpoint template: Adam m/v as params-shaped
        trees regardless of mode (STANDARD per-layer form, never the
        pipeline's stacked form), so a checkpoint written by a zero1 or
        pipeline run resumes a replicated run (and vice versa) at ANY
        topology — the same layout-independence contract as the CNN
        trainers (strategies/sync.py ``_opt_like``)."""
        zeros = jax.tree.map(
            lambda l: np.zeros(l.shape, np.float32), dict(self._host_like)
        )
        return AdamState(
            step=np.zeros((), np.int32),
            m=zeros,
            v=jax.tree.map(np.copy, zeros),
        )

    def _params_for_save(self, params):
        """Live params -> the checkpoint's standard host form (pipeline
        runs unstack their [L, ...] block leaves back to the per-layer
        list — the topology-free form every mode reads)."""
        host = multihost.replicate_for_host(self.mesh, params)
        if self._part is not None:
            return self._partition.unstack_blocks(
                jax.tree.map(np.asarray, host)
            )
        return host

    def _place_params(self, host_tree):
        """Checkpoint-form (standard) params -> this trainer's live
        placement (stacked over pp for pipeline runs; Megatron shards
        over tp; replicated otherwise)."""
        if self._part is not None:
            host_tree = self._partition.stack_blocks(
                jax.tree.map(np.asarray, host_tree)
            )
        return multihost.put_tree(self.mesh, self._pspecs, host_tree)

    def _result_params(self, params):
        """Live params -> the LMResult host tree (standard form in every
        mode, so downstream comparisons never see the stacked layout)."""
        host = jax.device_get(params)
        if self._part is not None:
            return self._partition.unstack_blocks(host)
        return host

    def _opt_for_save(self, opt_state):
        """Convert the live optimizer state to the checkpoint form."""
        if self._part is not None:
            # Pipeline: gather the pp/tp-sharded stacked m/v and unstack
            # to the standard per-layer form (same layout-free contract
            # as every other mode).
            m, v = multihost.replicate_for_host(
                self.mesh, (opt_state.m, opt_state.v)
            )
            unstack = lambda t: self._partition.unstack_blocks(
                jax.tree.map(np.asarray, t)
            )
            return AdamState(
                step=np.asarray(opt_state.step), m=unstack(m), v=unstack(v)
            )
        if self._hplan is not None:
            # Hybrid: gather the flat (dp, sp) chunks AND the tp shards
            # (replicate_for_host reassembles each tp-sharded leaf), then
            # interleave back into one params-shaped tree — the same
            # layout-free form every other mode writes.
            m_flat, v_flat, m_tp, v_tp = multihost.replicate_for_host(
                self.mesh,
                (opt_state.m_flat, opt_state.v_flat,
                 opt_state.m_tp, opt_state.v_tp),
            )
            rebuild = lambda flat, tp: jax.tree.map(
                np.asarray,
                self._hplan.merge(
                    self._hplan.unflatten_rep(jnp.asarray(flat)), list(tp)
                ),
            )
            return AdamState(
                step=np.asarray(opt_state.step),
                m=rebuild(m_flat, m_tp),
                v=rebuild(v_flat, v_tp),
            )
        if not self.config.zero1:
            return multihost.replicate_for_host(self.mesh, opt_state)
        m, v = multihost.replicate_for_host(
            self.mesh, (opt_state.m, opt_state.v)
        )
        # Strip the chunk padding before unflattening — ravel_pytree's
        # unravel consumes exactly `total` elements.
        unflat = lambda flat: jax.tree.map(
            np.asarray,
            self._plan.unflatten(jnp.asarray(flat)[: self._plan.total]),
        )
        return AdamState(
            step=np.asarray(opt_state.step), m=unflat(m), v=unflat(v)
        )

    def _place_opt(self, opt_tree):
        """Re-place a checkpoint-form optimizer state onto this trainer's
        mode: replicated AdamState, flat chunks sharded over the mesh, or
        the hybrid split (elastic across ALL of them: a zero1 x tp save
        resumes replicated, tp-only, zero1-only, or at another
        topology — and vice versa)."""
        if self._part is not None:
            # Pipeline: stack the standard-form m/v into the [L, ...]
            # block leaves and place like the params (stage-resident
            # over pp, Megatron shards over tp).
            stack = lambda t: self._partition.stack_blocks(
                jax.tree.map(lambda a: np.asarray(a, np.float32), t)
            )
            return multihost.put_tree(
                self.mesh, self._opt_specs,
                AdamState(step=np.asarray(opt_tree.step),
                          m=stack(opt_tree.m), v=stack(opt_tree.v)),
            )
        if self._hplan is not None:
            n_dev = self.config.data_parallel * self.config.num_workers
            chunk = coll.chunk_size(self._hplan.rep_total, n_dev)

            def refit(tree):
                rep, tp = self._hplan.split(tree)
                flat = np.pad(
                    np.asarray(self._hplan.flatten_rep(
                        [np.asarray(l, np.float32) for l in rep]
                    )),
                    (0, n_dev * chunk - self._hplan.rep_total),
                )
                return (
                    multihost.put(self.mesh, P(AXES), flat),
                    [multihost.put(self.mesh, s, np.asarray(l, np.float32))
                     for s, l in zip(self._hplan.tp_specs, tp)],
                )

            m_flat, m_tp = refit(opt_tree.m)
            v_flat, v_tp = refit(opt_tree.v)
            return HybridAdam(
                step=multihost.put(self.mesh, P(),
                                   np.asarray(opt_tree.step)),
                m_flat=m_flat, v_flat=v_flat, m_tp=m_tp, v_tp=v_tp,
            )
        if not self.config.zero1:
            return multihost.put_tree(self.mesh, self._opt_specs, opt_tree)
        n_dev = self.config.data_parallel * self.config.num_workers
        chunk = coll.chunk_size(self._plan.total, n_dev)
        refit = lambda tree: multihost.put(
            self.mesh, P(AXES),
            np.pad(np.asarray(_FlatPlan.flatten(tree)),
                   (0, n_dev * chunk - self._plan.total)),
        )
        return ShardedAdam(
            step=multihost.put(self.mesh, P(), np.asarray(opt_tree.step)),
            m=refit(opt_tree.m),
            v=refit(opt_tree.v),
        )

    # -- training ----------------------------------------------------------

    def train(
        self,
        log=print,
        *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        resume=False,
        profile_dir: str | None = None,
        should_stop=None,
        dispatch_timeout: float = 0.0,
        metrics=None,
        metrics_interval: int = 10,
        metrics_writer=None,
        tracer=None,
        guard: bool = False,
        max_bad_steps: int = 0,
        max_rollbacks: int = 3,
        fault_injector=None,
        checkpoint_keep: int = 2,
        peak_flops: float | None = None,
        ici_bw: float | None = None,
        anomaly_detector=None,
    ) -> LMResult:
        """Same persistence/observability contract as every other trainer:
        atomic rolling checkpoint at epoch ends (plus every
        ``checkpoint_every`` batches), cross-cadence elastic resume via
        ``resume_plan``, graceful preemption through ``check_preempt``,
        ``dispatch_timeout`` accelerator-death watchdog, ``jax.profiler``
        trace under ``profile_dir``. The LM step has no RNG (no dropout),
        so a resumed run is bit-identical to an uninterrupted one.

        Telemetry (ISSUE 5): ``metrics`` is an ``obs.MetricRegistry``
        — when given, the span programs compute in-graph health signals
        (``obs.health``) and the trainer fetches them BATCHED on spans
        crossing ``metrics_interval`` global steps (never per step —
        the hot path gains no sync; with ``metrics=None`` the compiled
        programs are byte-identical to the pre-observability ones).
        ``metrics_writer`` (an ``obs.MetricsWriter``) is flushed on its
        own interval from the span loop. ``tracer`` (``obs.Tracer``)
        wraps every span dispatch and eval in host wall-clock spans.

        Resilience (ISSUE 6): ``resume`` accepts ``"auto"`` (newest
        VALID checkpoint — corrupt/truncated saves skipped by
        ``find_latest_valid``); saves retain the last
        ``checkpoint_keep`` step-stamped files. ``guard=True`` (implied
        by ``max_bad_steps > 0``) compiles the NaN-guarded step in
        EVERY mode (replicated / zero1 / hybrid / pipeline):
        a non-finite gradient applies identity in-graph, and
        ``max_bad_steps`` consecutive skips roll back to the last good
        checkpoint and replay from its step — the data stream is
        indexed by global step, so position IS the re-seed.
        ``fault_injector`` (``resilience.faults``) is the deterministic
        chaos hook the tests and ``--inject-fault`` drive.

        Time attribution (ISSUE 11): with ``metrics`` on, every
        bracket the loop already closes is attributed to one
        ``obs.goodput`` train phase — compute (the span dispatch, with
        a guarded span's skipped-step share re-filed as stall),
        staging, compile, eval, checkpoint_io, and rollback stall —
        published live as ``time_in_seconds{phase=}`` /
        ``goodput_fraction`` gauges next to ``train_mfu``; the pinned
        identity is that the phases sum to the observed bracket time.
        ``anomaly_detector`` (``obs.anomaly``, same registry as
        ``metrics``) is scored once per span over ``step_time``
        (span seconds per step) and ``mfu``."""
        cfg = self.config
        if tracer is None:
            tracer = NULL_TRACER
        ds = self.dataset
        bs = cfg.batch_size
        # batch_size vs num_train is validated in __init__ (every config
        # pre-flight lives there, so the CLI's ValueError guard can wrap
        # construction only — round-4 advisor).
        batch_num = ds.num_train // bs
        inj = fault_injector
        guard_on = bool(guard) or max_bad_steps > 0
        monitor = None
        if guard_on:
            from ..resilience.guard import GuardMonitor

            monitor = GuardMonitor(max_bad_steps,
                                   max_rollbacks=max_rollbacks,
                                   registry=metrics, tracer=tracer)

        def _stage_ws():
            # The grad-fault injection point: one poisoned loss weight
            # drives that batch's loss — and so every gradient — non-
            # finite through the REAL forward (no mock grads anywhere).
            w = ds.weights
            if inj is not None and inj.poisons_data():
                w = inj.poison_batches(np.asarray(w), batch_num, bs)
            return self.stage_batches(w, batch_num, bs)

        # Goodput attribution (ISSUE 11, obs.goodput): host arithmetic
        # on brackets the loop already closes — absent entirely with
        # metrics off, so the off path gains no clock reads.
        gp = None
        if metrics is not None:
            from ..obs.goodput import GoodputTracker

            gp = GoodputTracker(metrics, "train")
        if anomaly_detector is not None and (
                metrics is None or anomaly_detector.registry is not metrics):
            raise ValueError(
                "anomaly_detector must be built on the registry passed "
                "as metrics= (its anomaly_* metrics would otherwise land "
                "where nothing reads them)"
            )
        t_stage0 = time.perf_counter() if gp is not None else 0.0
        xs = self.stage_batches(ds.tokens, batch_num, bs)
        ys = self.stage_batches(ds.targets, batch_num, bs)
        ws = _stage_ws()
        put_test = lambda a: multihost.put(
            self.mesh, self._seq_spec(2), self._permuted(a)
        )
        xte = put_test(ds.test_tokens)
        yte = put_test(ds.test_targets)
        wte = put_test(ds.test_weights)
        # Fresh buffers: the span programs donate params/opt (on TPU),
        # which must never consume the trainer's own state.
        params = jax.tree.map(jnp.copy, self.params)
        opt_state = jax.tree.map(jnp.copy, self.opt_state)
        ckpt = checkpoint_file(checkpoint_dir)
        # Resume template in CHECKPOINT form: standard params-shaped
        # trees in every mode (a pipeline run's live params are stacked,
        # but its checkpoints — like everyone else's — are not).
        like = {"params": dict(self._host_like), "opt": self._opt_like()}
        tree, start_step = try_resume(ckpt, resume, like, log)
        if tree is not None:
            params = self._place_params(tree["params"])
            opt_state = self._place_opt(tree["opt"])
        guarded(
            lambda: force((xs, ys, ws, xte, yte, wte, params, opt_state)),
            dispatch_timeout, "train-set staging",
        )
        if gp is not None:
            # The whole host->device upload: stage_batches' async puts
            # complete at the force barrier just closed.
            gp.add("staging", time.perf_counter() - t_stage0)

        spans = eval_spans(batch_num, cfg.eval_every)
        resume_epoch, resume_spans = resume_plan(
            start_step, batch_num, cfg.eval_every, spans
        )
        health_on = metrics is not None
        fns: dict[int, Any] = {}
        compile_time = 0.0
        # Live resource accounting (ISSUE 10, obs.cost/obs.memory):
        # analytic per-step FLOPs for the train_mfu gauge (exact,
        # config-parameterized; topology re-shards the same math so the
        # number is mode-invariant — the mesh size enters the MFU
        # denominator instead), the per-device peak, and a memory
        # watermark sampler. All None/absent with metrics off — the
        # compiled programs never change (host-side arithmetic only).
        step_flops = n_dev = peak = mem_sampler = mfu_of = None
        bw = _comms = None
        # Per-program collective ledgers (ISSUE 20, obs.comms): the
        # span programs' static collective bytes, captured once per
        # compile for the comms roofline gauges below. Keyed by k —
        # per-STEP bytes divide the span's total by its step count.
        span_comm_bytes: dict[int, int] = {}
        if metrics is not None:
            from ..obs import comms as _comms
            from ..obs import cost as _cost
            from ..obs.memory import MemorySampler, record_compile

            mfu_of = _cost.mfu
            step_flops = _cost.lm_train_step_flops(
                cfg.spec, bs, ds.seq_len, remat=cfg.remat
            )
            n_dev = int(self.mesh.devices.size)
            # Policy-aware denominator (ISSUE 19): an fp32 run anchors
            # to the fp32 peak, not the table's bf16 row.
            peak = _cost.peak_flops_per_device(
                self.mesh.devices.flat[0], peak_flops,
                precision=cfg.policy().mfu_kind,
            )
            bw = _comms.ici_bw_per_device(self.mesh.devices.flat[0], ici_bw)
            mem_sampler = MemorySampler(metrics, self.mesh.devices.flat)

        def fn_for(k: int):
            # On-demand: a guard rollback can realign spans onto
            # lengths the initial plan never compiled.
            nonlocal compile_time
            if k not in fns:
                tc = time.perf_counter()
                fns[k] = (
                    self.span_program(k, health=health_on, guard=guard_on)
                    .lower(params, opt_state, xs, ys, ws, jnp.int32(0))
                    .compile()
                )
                t1 = time.perf_counter()
                compile_time += t1 - tc
                if metrics is not None:
                    # Compile-activity accounting (obs.memory): a build
                    # AFTER the AOT plan (a rollback realignment) is a
                    # mid-run latency incident — now auditable.
                    record_compile(metrics, tracer, "train_span",
                                   t0=tc, t1=t1, k=k)
                    gp.add("compile", t1 - tc)
                    # Static collective ledger (ISSUE 20, obs.comms):
                    # the program's bytes-on-the-wire, published once
                    # per distinct compile. Registry-gated like the
                    # clock reads — with metrics off the HLO text is
                    # never even fetched.
                    led = _comms.publish_program_ledger(
                        metrics, _comms.program_text(fns[k]),
                        program=f"train_span[{k}]", mesh=self.mesh,
                    )
                    span_comm_bytes[k] = led["total_bytes"]
            return fns[k]

        t0 = time.perf_counter()
        for k in {k for _, k, _ in spans} | {k for _, k, _ in resume_spans}:
            fn_for(k)
        te0 = time.perf_counter()
        ev = self._eval_fn().lower(params, xte, yte, wte).compile()
        compile_time = time.perf_counter() - t0
        if metrics is not None:
            te1 = time.perf_counter()
            record_compile(metrics, tracer, "eval", t0=te0, t1=te1)
            gp.add("compile", te1 - te0)
            _comms.publish_program_ledger(
                metrics, _comms.program_text(ev),
                program="eval[0]", mesh=self.mesh,
            )

        def _rollback():
            """Guard escalation: restore the newest VALID checkpoint at
            or before the divergence streak's first bad step (pruning
            the abandoned newer saves — resilience.guard.rollback_state
            owns the shared bookkeeping), heal a transient injected
            fault (restaging clean weights), and return the step to
            re-enter the span loop at."""
            nonlocal params, opt_state, ws
            from ..resilience.guard import rollback_state

            rtree, rstep = rollback_state(checkpoint_dir, monitor, like, log)
            params = self._place_params(rtree["params"])
            opt_state = self._place_opt(rtree["opt"])
            if inj is not None and inj.heal():
                ws = _stage_ws()
            force((ws, params, opt_state))
            return rstep

        timer = StepTimer()
        history: list[tuple[int, int, float]] = []
        accuracy = float("nan")
        loss = float("nan")
        tokens_per_batch = bs * ds.seq_len
        hit = preempted = False
        epoch = 0  # epochs=0: eval-only run (the loop never binds it)
        span_idx = 0
        resumed_from = start_step
        start = time.perf_counter()
        with trace(profile_dir):
            while True:
                rolled = False
                resume_epoch, resume_spans = resume_plan(
                    start_step, batch_num, cfg.eval_every, spans
                )
                for epoch in range(cfg.epochs):
                    for first, k, eval_after in (
                        resume_spans if epoch == resume_epoch else spans
                    ):
                        gstep = epoch * batch_num + first
                        if gstep < start_step:
                            continue  # already done by the resumed run
                        span_idx += 1
                        compile_before = compile_time
                        with timer.step(images=k * tokens_per_batch), \
                                tracer.span("train/span", gstep=gstep, k=k):
                            out = fn_for(k)(
                                params, opt_state, xs, ys, ws, jnp.int32(first)
                            )
                            params, opt_state, l = out[0], out[1], out[2]
                            hstack = out[3] if health_on else None
                            skipped = out[-1] if guard_on else None
                            # The span's loss is needed on the host anyway;
                            # its fetch closes the timing bracket.
                            loss = guarded(
                                lambda: float(l), dispatch_timeout,
                                f"span dispatch at global batch {gstep}",
                            )
                        # One host fetch of the [k] skip flags, shared
                        # by the goodput stall split and the guard
                        # monitor (the span barrier already executed —
                        # no new sync).
                        skipped_host = (jax.device_get(skipped)
                                        if guard_on else None)
                        if metrics is not None:
                            span_s = timer._times[-1]  # the bracket just closed
                            metrics.gauge("train_loss").set(loss)
                            metrics.gauge("train_step").set(gstep + k)
                            metrics.histogram(
                                "train_span_seconds",
                                "wall seconds per dispatched span program",
                            ).observe(span_s)
                            metrics.gauge("train_tokens_per_sec").set(
                                k * tokens_per_batch / span_s if span_s else 0.0
                            )
                            # MFU (ISSUE 10): analytic FLOPs of the k
                            # steps just dispatched over what the mesh
                            # could do at peak in the measured bracket.
                            mfu_val = mfu_of(step_flops * k, span_s,
                                             n_dev, peak)
                            metrics.gauge("train_mfu").set(mfu_val)
                            # Comms roofline (ISSUE 20, obs.comms):
                            # the span program's static per-step bytes
                            # against the ICI bandwidth anchor, next
                            # to the FLOPs-vs-peak MFU — which wall
                            # the step leans on, live.
                            cb = span_comm_bytes.get(k, 0) / k
                            rl = _comms.roofline(step_flops, cb,
                                                 n_dev, peak, bw)
                            metrics.gauge("comms_bytes_per_step").set(cb)
                            metrics.gauge("comms_time_model_s").set(
                                rl["comms_time_model_s"])
                            metrics.gauge("compute_time_model_s").set(
                                rl["compute_time_model_s"])
                            metrics.gauge("step_time_model_s").set(
                                rl["step_time_model_s"])
                            metrics.gauge("comms_fraction").set(
                                rl["comms_fraction"])
                            sb = metrics.gauge("step_bound")
                            sb.set(float(rl["bound"] == "compute"),
                                   bound="compute")
                            sb.set(float(rl["bound"] == "comms"),
                                   bound="comms")
                            # Attribution (ISSUE 11): compile carve-
                            # out + compute/stall split, shared with
                            # the single-chip trainer in ONE helper so
                            # the pinned identities cannot drift.
                            from ..obs.goodput import \
                                attribute_train_span

                            attribute_train_span(
                                gp, span_s,
                                compile_time - compile_before,
                                int(np.sum(skipped_host))
                                if guard_on else 0, k,
                            )
                            if anomaly_detector is not None:
                                anomaly_detector.tick({
                                    "step_time": span_s / k,
                                    "mfu": mfu_val,
                                })
                            # The divergence tripwire reads EVERY span (a
                            # [k] int32 fetch riding the loss barrier — the
                            # span already executed, this adds no sync); the
                            # full norm dict is fetched batched only on
                            # spans crossing the metrics interval
                            # (save_crossed reused as the crossing
                            # predicate). Recorded BEFORE the guard can
                            # break to rollback, so even a tripping
                            # span's non-finite burst lands in the
                            # counter (the incident must be auditable).
                            hlt.record_nonfinite(
                                metrics,
                                jax.device_get(hstack["nonfinite_grads"]),
                            )
                            if save_crossed(gstep, k, metrics_interval,
                                            first + k == batch_num):
                                hlt.record_health(
                                    metrics, jax.device_get(hstack),
                                    include_nonfinite=False,
                                )
                                # Memory watermarks ride the SAME
                                # interval boundary (obs.memory): a
                                # host allocator query, self-latched
                                # off where unsupported — zero new
                                # device syncs on the hot path.
                                mem_sampler.sample()
                            if metrics_writer is not None:
                                metrics_writer.maybe_flush()
                        if guard_on and monitor.observe(
                            skipped_host, gstep
                        ):
                            t_rb0 = (time.perf_counter()
                                     if gp is not None else 0.0)
                            start_step = _rollback()
                            monitor.rolled_back(start_step)
                            if gp is not None:
                                # Restore + restage + replay re-entry:
                                # the fault-tolerance tax.
                                gp.add("stall",
                                       time.perf_counter() - t_rb0)
                            rolled = True
                            break
                        if eval_after:
                            t_ev0 = (time.perf_counter()
                                     if gp is not None else 0.0)
                            with tracer.span("train/eval", gstep=gstep + k):
                                accuracy = guarded(
                                    lambda: float(ev(params, xte, yte, wte)),
                                    dispatch_timeout,
                                    f"eval after batch {first + k - 1}",
                                )
                            if gp is not None:
                                gp.add("eval",
                                       time.perf_counter() - t_ev0)
                            if metrics is not None:
                                metrics.gauge("train_eval_accuracy").set(accuracy)
                            history.append((epoch, first + k - 1, accuracy))
                            log(
                                f"epoch {epoch} batch {first + k - 1} "
                                f"loss {loss:.4f} test_accuracy {accuracy:.4f}"
                            )
                            # hit_target duck-types on .target_accuracy, which
                            # SeqConfig shares with TrainConfig.
                            hit = hit_target(cfg, accuracy)
                        if inj is not None:
                            inj.maybe_sigterm(gstep + k)
                        preempted = preempted or check_preempt(
                            should_stop, log, ckpt is not None, span_idx
                        )
                        if ckpt and save_crossed(
                            gstep, k, checkpoint_every,
                            first + k == batch_num or hit or preempted,
                        ):
                            t_ck0 = (time.perf_counter()
                                     if gp is not None else 0.0)
                            save_checkpoint(
                                ckpt,
                                {"params": self._params_for_save(params),
                                 "opt": self._opt_for_save(opt_state)},
                                step=gstep + k, extra={"epoch": epoch},
                                keep=checkpoint_keep,
                            )
                            if gp is not None:
                                gp.add("checkpoint_io",
                                       time.perf_counter() - t_ck0)
                        if hit or preempted:
                            break
                    if hit:
                        log(f"target accuracy {cfg.target_accuracy} reached")
                    if rolled or hit or preempted:
                        break
                if not rolled:
                    break
        wall = time.perf_counter() - start

        if not (history and history[-1][:2] == (epoch, batch_num - 1)) and not hit:
            t_ev0 = time.perf_counter() if gp is not None else 0.0
            accuracy = guarded(
                lambda: float(ev(params, xte, yte, wte)),
                dispatch_timeout, "final eval",
            )
            if gp is not None:
                gp.add("eval", time.perf_counter() - t_ev0)
            if not preempted:
                # A preempted run's history must not claim an eval point
                # after batches that never trained; final_accuracy still
                # reports the stopped state.
                history.append((epoch, batch_num - 1, accuracy))
        if gp is not None:
            # Final publish: the tail brackets (last eval/checkpoint)
            # land in the gauges even when no span follows them.
            gp.publish()
        stats = timer.stats()
        log(
            f"final test_accuracy {accuracy:.4f} loss {loss:.4f} "
            f"({stats.tokens_per_sec:.0f} tokens/s)"
        )
        return LMResult(
            params=self._result_params(params),
            final_accuracy=accuracy,
            final_loss=loss,
            wall_time_s=wall,
            train_time_s=stats.total_s,
            history=history,
            tokens_per_sec=stats.tokens_per_sec,
            compile_time_s=compile_time,
            step_stats=stats,
            resumed_from_step=resumed_from,
            preempted=preempted,
            skipped_steps=monitor.skipped_steps if monitor else 0,
            rollbacks=monitor.rollbacks if monitor else 0,
        )
