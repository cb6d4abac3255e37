"""ddl_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA re-design of the capabilities of
epikjjh/DIstributed-Deep-Learning (parameter-server MNIST training over MPI,
reference mounted at /root/reference): {sync, async} gradient aggregation ×
{unsharded, block-sharded, greedy-balanced-sharded} parameter-server state,
plus a single-chip baseline.

Where the reference moves fp32 numpy buffers over mpi4py between CPU
TensorFlow-1.x processes (reference: mnist_sync/worker.py:19-24,
mnist_sync/parameter_server.py:55-69), this framework expresses the same
semantics as XLA collectives over a `jax.sharding.Mesh`:

- sync aggregation        -> `psum` / `psum_scatter` under `shard_map`
- sharded param serving   -> `NamedSharding` placement + `all_gather`
- greedy load balancing   -> pluggable `LayoutPolicy` (zig-zag + LPT)
- async (Hogwild-ish) PS  -> host-dispatched per-device train islands with a
                             deterministic, seeded staleness schedule

Layout:
    data/       MNIST pipeline (reference model/model.py:6-14 semantics)
    models/     pure-JAX model zoo (MNIST CNN: model/model.py:17-106)
    ops/        optimizers (TF1-semantics Adam)
    parallel/   mesh, collectives, layout policies
    strategies/ sync (DP + ZeRO-1 sharded) and async (Hogwild PS) trainers
    train/      config + single-chip trainer
    utils/      metrics/profiling, checkpoint/resume
"""

__version__ = "0.1.0"
