"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so that every sharding /
multi-chip code path executes without TPU hardware (the driver separately
dry-runs the multi-chip path; see ``__graft_entry__.dryrun_multichip``).
The env vars must be set before the first ``import jax`` anywhere in the
test process, hence the top-of-module placement.

The reference's fixture analog: a single 1-CPU local Ray instance standing
in for the cluster (``tests/conftest.py:7-44`` in the reference).

``OUTDATED``: five tests of ``tests/chipbench/test_lfm2_family.py`` pin lists
that ISSUE 32 lengthens (a fourth cell, a third family, two more per-layer
metrics), and a PR that adds to the benchmark may edit no file the benchmark
already has. They are marked as expected to fail from here, outside the
benchmark's ``paths``, by their full node ids and strictly: if one passes
(the pin was brought up to date in place) the mark fails loudly and has to
go. ``tests/chipbench/test_laguna_family.py`` holds what each one guards,
stated so that it stays true when a family, a cell or a metric is appended.
A sixth is ``test_phi4flash_family.py``'s list of the entries that grew
since its parent (``keye-seq16k-train`` is appended to ``moe.*`` too);
``tests/chipbench/test_keye_family.py`` keeps what it guards, that every
accepted entry keeps its cells and what grew grew at the end. A
``benchmark`` PR can fold these marks, and the three of
``tests/chipbench/conftest.py`` (ISSUE 28), back into the pinned tests.
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


_LFM2_FAMILY = "tests/chipbench/test_lfm2_family.py::"
_LISTS_TWO_FAMILIES = (
    "pins ['dlrm', 'lfm2_moe'] as the whole list of families; ISSUE 32 adds laguna"
)
OUTDATED = {
    _LFM2_FAMILY + "test_the_cell_and_its_entries_are_additions_at_the_end":
        "pins lfm2-seq8k-train as the last cell, PR 28's three metrics as the "
        "last of per_layer and one cell a list; ISSUE 32 appends a cell and "
        "two metrics",
    _LFM2_FAMILY + "test_everything_the_benchmark_had_is_the_parent_s":
        "pins BENCHMARK.json less PR 28's entries to commit ac23546's digest; "
        "ISSUE 32 appends entries and a cell to thirteen lists",
    _LFM2_FAMILY
    + "test_a_missing_or_unknown_family_is_an_error_that_lists_every_family[None]":
        _LISTS_TWO_FAMILIES,
    _LFM2_FAMILY
    + "test_a_missing_or_unknown_family_is_an_error_that_lists_every_family"
    "[transformer-xl]": _LISTS_TWO_FAMILIES,
    _LFM2_FAMILY + "test_pr_25_s_entries_keep_their_place_keys_and_cells":
        "pins PR 28's three metrics as all that follows PR 25's and "
        "lfm2-seq8k-train as the one cell appended; ISSUE 32 appends to both",
    "tests/chipbench/test_phi4flash_family.py::"
    "test_what_this_pr_appended_follows_what_was_there":
        "pins the per-layer entries that grew since phi4flash's parent to the "
        "twelve its cell was appended to; keye-seq16k-train is appended to "
        "those and to moe.*, which phi4flash left",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = OUTDATED.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))


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
