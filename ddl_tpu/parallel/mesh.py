"""Device-mesh construction.

Replaces the reference's MPI rank topology (PS ranks ``0..num_ps-1``, worker
ranks ``num_ps..size-1``, mnist_sync_sharding/worker.py:60-66) with a JAX
``Mesh``. On TPU the "workers" are mesh positions along a data-parallel axis
riding ICI; the "parameter servers" disappear into shardings over the same
axis (SURVEY.md §5: the PS role becomes ``NamedSharding`` placement, the
handshake becomes a static layout computed at trace time).
"""

from __future__ import annotations

import math
import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis name for the data-parallel / shard axis. One 1-D axis covers
# the whole reference feature matrix: DP replicas and parameter shards are
# both laid out along it (ZeRO-style: shard count == worker count).
DP_AXIS = "dp"


def make_mesh(
    num_devices: int | None = None, *, axis: str = DP_AXIS, devices=None
) -> Mesh:
    """A 1-D mesh over ``num_devices`` (default: all local devices).

    The device order is ``jax.devices()`` order, which on TPU follows the
    physical ICI torus so neighbouring mesh positions are ICI neighbours —
    collectives along the axis ride ICI, never DCN.
    """
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devices)}"
            )
        devices = devices[:num_devices]
    if jax.process_count() > 1:
        # Multi-controller world: a mesh that skips a process entirely
        # leaves that process unable to build global arrays
        # (make_array_from_process_local_data has no addressable shard) —
        # surface it here instead of a StopIteration deep in staging.
        missing = set(range(jax.process_count())) - {
            d.process_index for d in devices
        }
        if missing:
            raise ValueError(
                f"mesh over {len(devices)} devices owns no row on "
                f"process(es) {sorted(missing)}; use a worker count that "
                "spans every process (e.g. --num-workers = the global "
                "device count)"
            )
    return Mesh(np.asarray(devices), (axis,))


# Second mesh axis for 2-D (data x sequence) parallelism: batch shards
# over DP_AXIS rows, sequence over SP_AXIS columns (strategies/seq.py).
SP_AXIS = "sp"


def make_mesh_2d(
    num_dp: int,
    num_sp: int,
    *,
    axes: tuple[str, str] = (DP_AXIS, SP_AXIS),
    devices=None,
) -> Mesh:
    """A ``[num_dp, num_sp]`` mesh over the first ``num_dp * num_sp``
    devices. ``jax.devices()`` order follows the physical ICI torus, and
    the minor (sp) axis is contiguous in it, so the sequence-parallel
    ring's ppermute hops ride neighbouring ICI links; dp collectives
    stride across rows (still ICI within a slice)."""
    return _mesh_nd((num_dp, num_sp), axes, devices)


# Tensor-parallel axis: Megatron-style column/row sharded block weights
# (strategies/seq.py tensor_parallel).
TP_AXIS = "tp"


def make_mesh_3d(
    num_dp: int,
    num_sp: int,
    num_tp: int,
    *,
    axes: tuple[str, str, str] = (DP_AXIS, SP_AXIS, TP_AXIS),
    devices=None,
) -> Mesh:
    """A ``[num_dp, num_sp, num_tp]`` mesh over the first ``dp*sp*tp``
    devices. The MINOR (tp) axis is contiguous in ``jax.devices()``
    order — tensor-parallel psums are the highest-frequency collective
    (two per block per step), so they get the neighbouring ICI links;
    the sp ring's ppermute strides by ``num_tp`` (still short ICI hops
    within a slice), and dp collectives stride widest."""
    return _mesh_nd((num_dp, num_sp, num_tp), axes, devices)


# Pipeline-parallel axis: the LAYER STACK splits into contiguous stages
# over it (ddl_tpu.pipeline). Activations (and cotangents on the
# backward) hop stage-to-stage via lax.ppermute each schedule tick.
PP_AXIS = "pp"


def make_mesh_4d(
    num_dp: int,
    num_sp: int,
    num_tp: int,
    num_pp: int,
    *,
    axes: tuple[str, str, str, str] = (DP_AXIS, SP_AXIS, TP_AXIS, PP_AXIS),
    devices=None,
) -> Mesh:
    """A ``[num_dp, num_sp, num_tp, num_pp]`` mesh over the first
    ``dp*sp*tp*pp`` devices. The MINOR (pp) axis is contiguous in
    ``jax.devices()`` order, so every stage hop — one activation
    ppermute forward and one cotangent ppermute backward per schedule
    tick — rides a neighbouring ICI link; tp psums stride by ``num_pp``
    (still short hops within a slice), sp and dp stride wider. A
    ``num_pp == 1`` topology should use :func:`make_mesh_3d` /
    :func:`make_mesh_2d` instead (byte-identical programs to the
    pre-pipeline stack)."""
    return _mesh_nd((num_dp, num_sp, num_tp, num_pp), axes, devices)


def _mesh_nd(shape: tuple[int, ...], axes: tuple[str, ...], devices) -> Mesh:
    """Shared builder behind the 2-D/3-D mesh constructors: validates
    sizes, slices the leading devices, and rejects topologies that leave
    a process owning no devices (one copy of the check — the 2-D/3-D
    twins diverging here would be invisible until a multi-process run)."""
    if min(shape) < 1:
        raise ValueError(
            "mesh axes must be >= 1, got " + "x".join(map(str, shape))
        )
    if devices is None:
        devices = jax.devices()
    n = math.prod(shape)
    if n > len(devices):
        raise ValueError(
            f"requested {'x'.join(map(str, shape))} devices, "
            f"have {len(devices)}"
        )
    devices = list(devices)[:n]
    if jax.process_count() > 1:
        missing = set(range(jax.process_count())) - {
            d.process_index for d in devices
        }
        if missing:
            raise ValueError(
                f"mesh over {n} devices owns no row on process(es) "
                f"{sorted(missing)}; use a topology that spans every process"
            )
    return Mesh(np.asarray(devices).reshape(shape), axes)


def extend_cpu_collective_timeouts(warn_s: int = 120, kill_s: int = 900) -> None:
    """Raise XLA:CPU's in-process collective rendezvous timeouts via
    XLA_FLAGS (effective only BEFORE the CPU backend initializes).

    The CPU runtime hard-aborts the process when the devices' threads do
    not all reach a collective within ~40s of each other
    (``rendezvous.cc`` "Termination timeout ... Exiting to ensure a
    consistent program state"). On a loaded single-core host, 8 virtual
    devices each running a multi-second program segment before a
    collective can legitimately exceed that skew — a full-width W=8
    per-worker eval was measured aborting this way. Flags already present
    in XLA_FLAGS are respected."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    add = []
    if "xla_cpu_collective_call_warn_stuck_timeout_seconds" not in flags:
        add.append(
            f"--xla_cpu_collective_call_warn_stuck_timeout_seconds={warn_s}"
        )
    if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
        add.append(
            f"--xla_cpu_collective_call_terminate_timeout_seconds={kill_s}"
        )
    if add:
        os.environ["XLA_FLAGS"] = (flags + " " + " ".join(add)).strip()


def virtual_cpu_mesh(n: int) -> None:
    """Point JAX at an ``n``-device virtual CPU platform — the
    hermetic surface every multi-chip strategy runs on when real chips
    are absent (tests, CI, smoke runs, the driver dryrun). Chosen only
    by an explicit request for CPU (``--platform cpu``, the test
    conftest, the dryrun entry); it never looks at, and never replaces,
    an accelerator backend.

    Sets the platform and the device count BEFORE any backend exists and
    initializes nothing itself. Called after the CPU backend is up it is
    a no-op when that backend already has ``n`` devices, and an error
    (:func:`set_cpu_device_count`) when it has fewer."""
    import jax

    # Only effective pre-init; harmless otherwise.
    extend_cpu_collective_timeouts()
    jax.config.update("jax_platforms", "cpu")
    set_cpu_device_count(n)


def set_cpu_device_count(n: int) -> None:
    """Size the virtual CPU platform at ``n`` devices through the
    ``jax_num_cpu_devices`` config — the one spelling the tests'
    conftest, ``--platform cpu`` and :func:`virtual_cpu_mesh` share.

    The config only takes effect before the backends initialize, and JAX
    refuses to change it afterwards. Asked again once they exist, this
    returns quietly when the live CPU backend already has at least ``n``
    devices (an in-process second ``main(["--platform", "cpu", ...])``,
    a test after the conftest) and raises a message naming both counts
    otherwise."""
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        have = len(jax.devices("cpu"))
        if have < n:
            raise RuntimeError(
                f"asked for a {n}-device virtual CPU platform, but the CPU "
                f"backend is already initialized with {have} device(s); "
                "the count must be set before the first JAX operation"
            ) from None


def require_tpu():
    """The measurement tools' device gate: the first device, which must
    be a TPU. A tool that measures the chip does not substitute another
    platform, wait for one, or relay an old number — with no TPU it
    stops here, non-zero, before any work."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"this tool measures a TPU; JAX found platform "
            f"{dev.platform!r} — no measurement taken"
        )
    return dev


def device_record() -> dict:
    """The device a result was produced on, as JAX reports it — the
    ``"device"`` entry every bench artifact and ``chip_smoke.py`` carry."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class AcceleratorTimeout(RuntimeError):
    """A watchdogged native call did not complete: the accelerator backend
    is presumed dead/unreachable. The wedged thread is STILL blocked in
    native code — after reporting, the process should exit via ``os._exit``
    (normal interpreter shutdown can re-enter the dead backend through
    atexit/PJRT destructors and hang anyway)."""


def run_within(fn, timeout_s: float, *, what: str = "operation"):
    """Run ``fn`` on a daemon watchdog thread; return its result, re-raise
    its exception, or raise :class:`AcceleratorTimeout` after ``timeout_s``
    seconds. The one shared wedged-native-call watchdog (training-span
    and eval barriers behind ``--dispatch-timeout``): a native backend
    call that never returns cannot be interrupted — only abandoned. See
    :class:`AcceleratorTimeout` for the post-timeout exit contract."""
    import threading

    outcome: list[tuple[bool, object]] = []

    def run():
        try:
            outcome.append((True, fn()))
        except BaseException as e:  # surface the real error, not a timeout
            outcome.append((False, e))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout_s)
    if not outcome:
        raise AcceleratorTimeout(
            f"{what} did not complete within {timeout_s:.0f}s"
        )
    ok, value = outcome[0]
    if not ok:
        raise value
    return value


def pallas_interpret_for(mesh: Mesh) -> bool:
    """Pallas kernel mode for this mesh: compiled (non-interpret) on TPU —
    the product path a real chip runs — and interpreter mode everywhere
    else (the CPU test meshes, where Mosaic cannot compile). Centralized so
    every kernel call site picks the same way and the selection is unit-
    testable without real hardware."""
    return mesh.devices.flat[0].platform != "tpu"


def donation_for(mesh: Mesh, *argnums: int) -> tuple[int, ...]:
    """Buffer-donation argnums for a jitted step on this mesh.

    On TPU, donating params/optimizer state halves peak HBM for the update.
    The in-process CPU runtime (the 8-device virtual test mesh) deadlocks in
    its AllReduce when replicated inputs are donated under shard_map, so
    donation is disabled there — correctness is identical either way.
    """
    if mesh.devices.flat[0].platform == "cpu" and mesh.devices.size > 1:
        return ()
    return argnums
