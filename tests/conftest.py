"""Test environment: an 8-device virtual CPU platform, so every multi-chip
strategy is exercised hermetically (SURVEY.md section 4b).

Two settings, both made before any backend exists: ``JAX_PLATFORMS=cpu``
(the tier-1 command exports it too) and the device count, through the
same ``mesh.virtual_cpu_mesh`` that ``--platform cpu`` uses — so an
in-process ``main([..., "--platform", "cpu", ...])`` later in the
session asks for the count that is already live. The persistent compile
cache stays off for the suite and the children it spawns
(``ddl_tpu.utils.compile_cache`` only places the directory)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# Raise the CPU in-process collective rendezvous abort threshold: on a
# loaded host the 8 device threads can legitimately skew past the
# default ~40s and the runtime HARD-ABORTS the process (see
# mesh.extend_cpu_collective_timeouts). 300s (not the 900s default):
# a REAL collective deadlock should still abort with the rendezvous
# diagnostic well inside a per-file test timeout.
from ddl_tpu.parallel.mesh import (  # noqa: E402
    extend_cpu_collective_timeouts,
    virtual_cpu_mesh,
)

extend_cpu_collective_timeouts(kill_s=300)
virtual_cpu_mesh(8)

import jax  # noqa: E402

assert len(jax.devices()) == 8, (
    f"tests need the 8-device virtual CPU mesh, got {jax.devices()}"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ddl_tpu.data import load_mnist  # noqa: E402
from ddl_tpu.models import cnn  # noqa: E402

# Narrow-width instance of the reference architecture family: identical
# structure (14 vars, 4 conv+pool stages, 2 dropout FCs) at ~1/400 the
# FLOPs, so multi-device integration tests fit a single-core CPU host.
# Full-width parity with the torch oracle is covered in test_model.py.
# Same widths as the CLI --tiny preset and the driver dryrun.
SMALL_SPECS = cnn.make_param_specs(
    conv_channels=cnn.TINY_CONV_CHANNELS, fc_sizes=cnn.TINY_FC_SIZES
)


@pytest.fixture(scope="session")
def small_dataset():
    """A small deterministic procedural dataset shared across tests."""
    return load_mnist(path=None, synthetic_train=2048, synthetic_test=512, seed=7)


@pytest.fixture(scope="session")
def small_params():
    """Params for the narrow test model (see SMALL_SPECS)."""
    return cnn.init_params(jax.random.PRNGKey(3), specs=SMALL_SPECS)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
