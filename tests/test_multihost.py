"""Multi-host launch path (SURVEY.md §5 distributed comm backend; parity
target: mpiexec MPMD spanning processes, mnist_sync/run.sh:3).

Real multi-host needs multiple hosts; what is testable on one box is
(a) the per-process data-feeding math as pure functions, (b) the
process-count=1 degenerate world end-to-end (jax.distributed.initialize +
CLI --multihost), and (c) that the trainers' placement path (multihost.put)
is exactly device_put in a 1-process world.
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ddl_tpu.parallel import multihost
from ddl_tpu.parallel.mesh import DP_AXIS, make_mesh


def test_local_worker_rows_single_process_owns_all():
    mesh = make_mesh(8)
    np.testing.assert_array_equal(
        multihost.local_worker_rows(mesh), np.arange(8)
    )


def test_sharded_dims_ignores_size_one_axes():
    """_sharded_dims drives put()'s multi-process slicing: axes of mesh
    size 1 (the dp row of a [1, W] lm mesh) must read as replicated."""
    from ddl_tpu.parallel.mesh import make_mesh_2d

    mesh = make_mesh_2d(1, 8)
    dims = multihost._sharded_dims(mesh, P(None, DP_AXIS, "sp"))
    assert dims == [(2, ("sp",), 8)]  # dp (size 1) contributes nothing
    assert multihost._sharded_dims(mesh, P()) == []
    combined = multihost._sharded_dims(mesh, P((DP_AXIS, "sp")))
    assert combined == [(0, (DP_AXIS, "sp"), 8)]


def test_axis_positions_single_process_owns_all():
    from ddl_tpu.parallel.mesh import make_mesh_2d

    mesh = make_mesh_2d(2, 4)
    np.testing.assert_array_equal(
        multihost._axis_positions(mesh, ("sp",)), np.arange(4)
    )
    np.testing.assert_array_equal(
        multihost._axis_positions(mesh, (DP_AXIS, "sp")), np.arange(8)
    )


def test_local_slice_extracts_owner_blocks():
    # 8-way split of 16 rows: process owning mesh rows [2, 3] must feed
    # global rows [4, 5, 6, 7] — the multi-process data-feeding math.
    a = np.arange(16 * 3).reshape(16, 3)
    out = multihost.local_slice(a, 0, 8, np.array([2, 3]))
    np.testing.assert_array_equal(out, a[4:8])
    # Axis 1 (the async [R, W, bs, ...] layout).
    b = np.arange(2 * 8 * 5).reshape(2, 8, 5)
    out = multihost.local_slice(b, 1, 8, np.array([7]))
    np.testing.assert_array_equal(out, b[:, 7:8])


def test_put_degenerates_to_device_put():
    mesh = make_mesh(8)
    a = np.arange(32, dtype=np.float32).reshape(8, 4)
    sharded = multihost.put(mesh, P(DP_AXIS), a)
    assert sharded.sharding == NamedSharding(mesh, P(DP_AXIS))
    np.testing.assert_array_equal(np.asarray(sharded), a)
    rep = multihost.put(mesh, P(), a)
    assert rep.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(rep), a)


def test_put_tree_single_spec_and_spec_tree():
    mesh = make_mesh(8)
    tree = {"a": np.zeros((8, 2), np.float32), "b": np.ones((4,), np.float32)}
    out = multihost.put_tree(mesh, P(), tree)
    assert out["a"].sharding.is_fully_replicated
    specs = {"a": P(DP_AXIS), "b": P()}
    out = multihost.put_tree(mesh, specs, tree)
    assert out["a"].sharding == NamedSharding(mesh, P(DP_AXIS))
    assert out["b"].sharding.is_fully_replicated


class _FakeDev:
    def __init__(self, pid):
        self.process_index = pid


def _fake_mesh(shape: dict, owner) -> object:
    """A stand-in Mesh for the pure staging math: ``owner(coords) ->
    process id`` assigns every device. Lets the multi-dim slab path be
    pinned without a second OS process (the extraction logic is pure)."""
    import types

    dims = tuple(shape.values())
    devs = np.empty(dims, dtype=object)
    for idx in np.ndindex(*dims):
        devs[idx] = _FakeDev(owner(dict(zip(shape, idx))))
    return types.SimpleNamespace(
        axis_names=tuple(shape), shape=shape, devices=devs
    )


def test_check_rectangular_accepts_slabs_and_rejects_diagonals(monkeypatch):
    """The 3-D [dp, sp, tp] staging contract: a process whose devices
    form a full cartesian block over the sharded dims (the tp-world
    topology — process p owns the sp=p slab, all tp columns) passes and
    yields per-dim positions; a diagonal assignment (no block to hand
    ``make_array_from_process_local_data``) is rejected up front."""
    shape = {"dp": 1, "sp": 2, "tp": 2}
    slab = _fake_mesh(shape, lambda c: c["sp"])
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    # A leaf sharded over BOTH (dp, sp) [dim 0] and tp [dim 1] — the
    # hybrid optimizer's worst case. Process 1 = sp row 1, every tp.
    dims = [(0, ("dp", "sp"), 2), (1, ("tp",), 2)]
    pos = multihost._check_rectangular(slab, dims)
    np.testing.assert_array_equal(pos[0], [1])
    np.testing.assert_array_equal(pos[1], [0, 1])
    # The extraction those positions drive: one slab per dim.
    a = np.arange(4 * 6).reshape(4, 6)
    out = multihost.local_slice(a, 0, 2, pos[0])
    out = multihost.local_slice(out, 1, 2, pos[1])
    np.testing.assert_array_equal(out, a[2:4, :])
    # Diagonal ownership: process 0 holds (sp=0, tp=0) and (sp=1, tp=1).
    diag = _fake_mesh(shape, lambda c: int(c["sp"] != c["tp"]))
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    with pytest.raises(ValueError, match="rectangular"):
        multihost._check_rectangular(diag, dims)


def test_multihost_world_process_count_1():
    """The degenerate one-process world, end-to-end in a fresh interpreter:
    jax.distributed.initialize (self-hosted coordinator) -> CLI --multihost
    trains a tiny sync_sharding run on the virtual mesh."""
    proc = subprocess.run(
        [sys.executable, "-m", "ddl_tpu", "sync_sharding", "--multihost",
         "--num-processes", "1",
         "--platform", "cpu", "--tiny", "--num-workers", "8", "--num-ps", "4",
         "--batch-size", "16", "--synthetic-train", "256",
         "--synthetic-test", "64", "--eval-every", "0", "--json"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "multihost: process 0/1" in proc.stdout
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert 0.0 <= payload["final_accuracy"] <= 1.0


def test_multihost_initialize_explicit_world(tmp_path):
    """Explicit coordinator/process args (the multi-host launch shape) in a
    fresh interpreter, then jax.process_count()/local_worker_rows through
    the initialized world."""
    code = """
import jax
jax.config.update("jax_platforms", "cpu")
from ddl_tpu.parallel.mesh import set_cpu_device_count
set_cpu_device_count(4)
from ddl_tpu.parallel import multihost
from ddl_tpu.parallel.mesh import make_mesh
port = multihost.free_port()
multihost.initialize(f"localhost:{port}", num_processes=1, process_id=0)
assert multihost.process_count() == 1
mesh = make_mesh(4)
import numpy as np
rows = multihost.local_worker_rows(mesh)
np.testing.assert_array_equal(rows, np.arange(4))
out = multihost.put(mesh, jax.sharding.PartitionSpec("dp"),
                    np.arange(8, dtype=np.float32))
np.testing.assert_array_equal(np.asarray(out), np.arange(8))
multihost.shutdown()
print("EXPLICIT-WORLD-OK")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "EXPLICIT-WORLD-OK" in proc.stdout


def _run_world(cmds: list[list[str]], timeout: float) -> list[str]:
    """Launch one subprocess per command as a jax.distributed world, reap
    them all, and return their stdouts. Kills survivors on any failure (a
    hung collective would otherwise leak the children — and the coordinator
    port — past the test and stall pytest shutdown). Children get a clean
    platform env: the conftest CPU-mesh overrides must not leak in."""
    import os

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [
        subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for cmd in cmds
    ]
    try:
        outs = [p.communicate(timeout=timeout) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"process failed:\n{err[-2000:]}"
    return [out for out, _ in outs]


@pytest.mark.parametrize("variant,extra", [
    ("sync", []),
    # ZeRO-1 across processes: reduce-scatter / all-gather (and the shard
    # state split) cross the process boundary over gloo.
    ("sync_sharding", ["--num-ps", "2", "--layout", "flat"]),
    # Sharded Hogwild serve: the two all_to_all exchanges cross processes.
    ("async_sharding", ["--num-ps", "2"]),
])
def test_two_process_world_trains_end_to_end(variant, extra):
    """REAL multi-controller training — two OS processes (the analogue of
    the reference's mpiexec spanning nodes, mnist_sync/run.sh:3) join one
    jax.distributed world (gloo over localhost), each owning ONE cpu device
    of a 2-worker mesh, feeding its own data shard, and training to
    identical results. This is the multi-process path for real, not the
    process-count=1 degenerate case."""
    port = multihost.free_port()
    common = [
        sys.executable, "-m", "ddl_tpu", variant, "--multihost",
        "--coordinator", f"127.0.0.1:{port}", "--num-processes", "2",
        "--platform", "cpu", "--num-workers", "2", "--tiny",
        "--batch-size", "16", "--synthetic-train", "96",
        "--synthetic-test", "64", "--eval-every", "3", "--json",
    ] + extra
    outs = _run_world(
        [common + ["--process-id", str(i)] for i in (0, 1)], timeout=280
    )
    payloads = []
    for i, out in enumerate(outs):
        assert f"multihost: process {i}/2, 2 global devices" in out
        payloads.append(json.loads(out.strip().splitlines()[-1]))
    # Same SPMD program, same global data -> both controllers report the
    # identical result.
    assert payloads[0]["final_accuracy"] == payloads[1]["final_accuracy"]
    assert payloads[0]["step_stats"]["steps"] > 0
    assert payloads[0]["config"]["num_workers"] == 2


def test_mesh_skipping_a_process_is_rejected():
    """A mesh whose rows all land on one process would strand the others
    (no addressable shard to contribute); make_mesh must reject it with a
    clear error instead of the deep StopIteration it used to surface."""
    port = multihost.free_port()
    code = f"""
import jax
jax.config.update("jax_platforms", "cpu")
from ddl_tpu.parallel.mesh import set_cpu_device_count
set_cpu_device_count(2)
import sys
from ddl_tpu.parallel import multihost
from ddl_tpu.parallel.mesh import make_mesh
multihost.initialize("127.0.0.1:{port}", num_processes=2,
                     process_id=int(sys.argv[1]))
try:
    make_mesh(2)  # both rows on process 0
except ValueError as e:
    assert "owns no row" in str(e), e
    print("MESH-GUARD-OK")
multihost.shutdown()
"""
    outs = _run_world(
        [[sys.executable, "-c", code, str(i)] for i in (0, 1)], timeout=120
    )
    for out in outs:
        assert "MESH-GUARD-OK" in out


def test_multihost_worker_count_must_split_over_processes():
    """--num-workers not divisible by --num-processes on the CPU platform
    fails fast (the per-process device count could not make the global
    world equal the worker count)."""
    proc = subprocess.run(
        [sys.executable, "-m", "ddl_tpu", "sync", "--multihost",
         "--coordinator", "127.0.0.1:1", "--num-processes", "2",
         "--process-id", "0", "--platform", "cpu", "--num-workers", "3"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "not divisible by" in proc.stderr


@pytest.mark.parametrize("variant,extra", [
    ("sync", []),
    # Sharded: the preemption save exercises the cross-process
    # replicate_for_host + logical-order conversion of ZeRO-1 m/v.
    ("sync_sharding", ["--num-ps", "2", "--layout", "flat"]),
])
def test_preemption_agreement_across_processes(tmp_path, variant, extra):
    """SIGTERM delivered to ONE process of a two-process world: the
    preemption flag goes through multihost.agree_flag, so BOTH controllers
    stop at the same span (mismatched stop points would deadlock the next
    span's collectives), checkpoint, and exit 0."""
    import os
    import signal as sig

    port = multihost.free_port()
    d = str(tmp_path / "ck")
    common = [
        sys.executable, "-m", "ddl_tpu", variant, "--multihost",
        "--coordinator", f"127.0.0.1:{port}", "--num-processes", "2",
        "--platform", "cpu", "--num-workers", "2", "--tiny",
        "--batch-size", "16", "--synthetic-train", "96",
        "--synthetic-test", "64", "--eval-every", "2", "--epochs", "200",
        "--checkpoint-dir", d, "--json",
    ] + extra
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONUNBUFFERED"] = "1"
    procs = [
        subprocess.Popen(
            common + ["--process-id", str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in (0, 1)
    ]
    try:
        for line in procs[0].stdout:
            if line.startswith("epoch:"):
                procs[0].send_signal(sig.SIGTERM)  # process 0 ONLY
                break
        outs = [p.communicate(timeout=280) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"process failed:\n{err[-2000:]}"
        payload = json.loads(out.strip().splitlines()[-1])
        assert payload["preempted"] is True  # both, though only p0 was signaled
    assert os.path.exists(os.path.join(d, "ckpt.npz"))


_RING_WORLD = """
import sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")  # before any backend touch
import jax.numpy as jnp

from ddl_tpu.parallel import multihost, ring
from ddl_tpu.parallel.mesh import DP_AXIS, make_mesh

multihost.initialize(coordinator_address="127.0.0.1:{port}",
                     num_processes=2, process_id={pid})
assert jax.process_count() == 2
mesh = make_mesh(2)

B, T, H, D = 2, 16, 2, 8
rng = np.random.default_rng(0)  # same seed both processes: identical input
q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
           for _ in range(3))
oracle = ring.full_attention(q, k, v, causal=True)

spec = jax.sharding.PartitionSpec(None, DP_AXIS)
qs, ks, vs = (multihost.put(mesh, spec, np.asarray(a)) for a in (q, k, v))
out = ring.make_ring_attention(mesh, causal=True)(qs, ks, vs)

from jax.experimental import multihost_utils
got = multihost_utils.process_allgather(out, tiled=True)
assert got.shape == oracle.shape, (got.shape, oracle.shape)
np.testing.assert_allclose(np.asarray(got), np.asarray(oracle), atol=2e-4)
print("RING-WORLD-OK")
multihost.shutdown()
"""


def test_two_process_ring_attention():
    """Ring attention across a REAL two-process world: the ppermute ring
    crosses the OS-process boundary over gloo (the DCN analogue), and the
    result still matches the single-device oracle exactly. Long-context
    sequence parallelism composes with the multi-host backend."""
    port = multihost.free_port()
    outs = _run_world(
        [[sys.executable, "-c",
          _RING_WORLD.format(port=port, pid=pid)] for pid in (0, 1)],
        timeout=280,
    )
    for out in outs:
        assert "RING-WORLD-OK" in out


def test_two_process_lm_world_trains_end_to_end():
    """The lm variant across a REAL two-process world: each process owns
    one device of the 2-way sequence-parallel mesh, so every ring-attention
    ppermute hop in training (fwd AND the transposed grads) crosses the
    OS-process boundary over gloo; both controllers report the identical
    result."""
    port = multihost.free_port()
    common = [
        sys.executable, "-m", "ddl_tpu", "lm", "--multihost",
        "--coordinator", f"127.0.0.1:{port}", "--num-processes", "2",
        "--platform", "cpu", "--num-workers", "2", "--seq-scheme", "ring",
        "--seq-len", "32", "--vocab", "16", "--d-model", "32", "--heads",
        "2", "--layers", "2", "--d-ff", "64", "--train-seqs", "32",
        "--test-seqs", "16", "--batch-size", "16", "--eval-every", "0",
        "--json",
    ]
    outs = _run_world(
        [common + ["--process-id", str(i)] for i in (0, 1)], timeout=280
    )
    payloads = []
    for i, out in enumerate(outs):
        assert f"multihost: process {i}/2, 2 global devices" in out
        payloads.append(json.loads(out.strip().splitlines()[-1]))
    assert payloads[0]["final_accuracy"] == payloads[1]["final_accuracy"]
    assert payloads[0]["final_loss"] == payloads[1]["final_loss"]
    assert payloads[0]["config"]["scheme"] == "ring"


def test_two_process_tp_world_trains_end_to_end():
    """Tensor parallelism across a REAL two-process world — the lifted
    single-controller restriction: a 1x2x2 [dp, sp, tp] mesh spans two
    OS processes (two cpu devices each; process p owns the sp=p slab),
    so every Megatron completion psum rides gloo between tp peers
    in-process while the ring's ppermute and — with --zero1 — the
    hybrid sharded optimizer's reduce-scatter/all-gather over the
    combined (dp, sp) axes cross the process boundary. Staging
    exercises multihost.put's multi-dim path: tp-sharded param leaves
    slice their tp dim, the (dp, sp)-flat optimizer chunks slice theirs,
    and the tp-replicated data dims stay slabs. Both controllers report
    identical results."""
    port = multihost.free_port()
    common = [
        sys.executable, "-m", "ddl_tpu", "lm", "--multihost",
        "--coordinator", f"127.0.0.1:{port}", "--num-processes", "2",
        "--platform", "cpu", "--num-workers", "2", "--tensor-parallel",
        "2", "--zero1", "--seq-scheme", "ring", "--seq-len", "32",
        "--vocab", "16", "--d-model", "32", "--heads", "2", "--layers",
        "2", "--d-ff", "64", "--train-seqs", "32", "--test-seqs", "16",
        "--batch-size", "16", "--eval-every", "0", "--json",
    ]
    outs = _run_world(
        [common + ["--process-id", str(i)] for i in (0, 1)], timeout=280
    )
    payloads = []
    for i, out in enumerate(outs):
        assert f"multihost: process {i}/2, 4 global devices" in out
        payloads.append(json.loads(out.strip().splitlines()[-1]))
    assert payloads[0]["final_loss"] == payloads[1]["final_loss"]
    assert payloads[0]["final_accuracy"] == payloads[1]["final_accuracy"]
    assert payloads[0]["config"]["tensor_parallel"] == 2
    assert payloads[0]["config"]["zero1"] is True


def test_two_process_lm_world_zigzag_matches_contiguous():
    """The balanced zigzag layout across a REAL two-process world: the
    travelling kpos crosses the OS-process boundary with its K/V block,
    and the permuted staging happens per-controller — the run must agree
    with the contiguous-layout world on the same config (same math,
    different placement; attention-reassociation tolerance)."""
    results = {}
    for layout in ("contiguous", "zigzag"):
        port = multihost.free_port()
        common = [
            sys.executable, "-m", "ddl_tpu", "lm", "--multihost",
            "--coordinator", f"127.0.0.1:{port}", "--num-processes", "2",
            "--platform", "cpu", "--num-workers", "2", "--seq-scheme",
            "ring", "--seq-layout", layout, "--seq-len", "32", "--vocab",
            "16", "--d-model", "32", "--heads", "2", "--layers", "2",
            "--d-ff", "64", "--train-seqs", "32", "--test-seqs", "16",
            "--batch-size", "16", "--eval-every", "0", "--json",
        ]
        outs = _run_world(
            [common + ["--process-id", str(i)] for i in (0, 1)], timeout=280
        )
        payloads = [json.loads(o.strip().splitlines()[-1]) for o in outs]
        assert payloads[0]["final_loss"] == payloads[1]["final_loss"]
        results[layout] = payloads[0]
    assert np.isclose(
        results["zigzag"]["final_loss"],
        results["contiguous"]["final_loss"], rtol=1e-3,
    ), results
    assert abs(results["zigzag"]["final_accuracy"]
               - results["contiguous"]["final_accuracy"]) < 0.05
