"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so that every sharding /
multi-chip code path executes without TPU hardware (the driver separately
dry-runs the multi-chip path; see ``__graft_entry__.dryrun_multichip``).
The env vars must be set before the first ``import jax`` anywhere in the
test process, hence the top-of-module placement.

The reference's fixture analog: a single 1-CPU local Ray instance standing
in for the cluster (``tests/conftest.py:7-44`` in the reference).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest

from ray_shuffling_data_loader_tpu import runtime


@pytest.fixture(scope="module")
def local_runtime():
    """Module-scoped runtime session (analog of the reference's module-scoped
    ``ray_start_regular_shared`` fixture)."""
    ctx = runtime.init(num_workers=2)
    yield ctx
    runtime.shutdown()


@pytest.fixture
def index_schedule_pinned(monkeypatch):
    """For tests of what the index schedule DELIVERS. Whether it may run
    is otherwise ``shuffle._index_schedule_allowed``'s reading of a
    stopwatch taken once a process (``_probed_host_costs``): a starved
    xdist worker times the 2 MB gather 4-25 x slow and the policy then
    says no for every test of that process. The cache must still be hot
    (``_DecodeCache.hot_refs``), so a cold epoch stays ``mapreduce``."""
    monkeypatch.setenv("RSDL_INDEX_SHUFFLE", "on")
