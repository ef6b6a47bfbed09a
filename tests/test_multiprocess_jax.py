"""Multi-process JAX delivery: the pod path, on two CPU processes.

VERDICT r1 item 7: the ``jax.make_array_from_process_local_data`` branch of
``JaxShufflingDataset._put`` (the SURVEY §7 M3 pod-sharded global batch)
was never executed by a test. Here two real processes under
``jax.distributed`` (4 virtual CPU devices each -> one 8-device global
mesh) each consume their trainer rank's shard and assemble global arrays;
a jitted global-mean step then forces the cross-process collective.

Reference analog: the Horovod example's multi-worker consumption
(``/root/reference/examples/horovod/ray_torch_shuffle.py:319-344``).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Parameters reach the worker script via env (RSDL_T_*) — .format braces
# and python -c quoting stay out of the picture.
_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["RSDL_T_REPO"])

import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["RSDL_T_COORD"],
    num_processes=2,
    process_id=int(os.environ["RSDL_T_RANK"]),
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())

import numpy as np
import jax.numpy as jnp
from jax.experimental import multihost_utils
from jax.sharding import Mesh

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data

rank = int(os.environ["RSDL_T_RANK"])
rdv = os.environ["RSDL_T_RDV"]
batch_size = 500

if rank == 0:
    ctx = runtime.init(num_workers=2)
    filenames, _ = generate_data(8000, 4, 1, 0.0, rdv + "/data")
    with open(rdv + "/runtime_dir.tmp", "w") as f:
        f.write(ctx.runtime_dir)
    os.rename(rdv + "/runtime_dir.tmp", rdv + "/runtime_dir")
else:
    deadline = time.time() + 120
    while not os.path.exists(rdv + "/runtime_dir"):
        assert time.time() < deadline, "rank0 session never appeared"
        time.sleep(0.2)
    with open(rdv + "/runtime_dir") as f:
        runtime.init(address=f.read().strip(), num_workers=2)
    filenames = sorted(
        os.path.join(rdv, "data", f) for f in os.listdir(rdv + "/data")
    )

mesh = Mesh(np.array(jax.devices()), ("data",))
ds = JaxShufflingDataset(
    filenames,
    num_epochs=1,
    num_trainers=2,
    batch_size=batch_size,
    rank=rank,
    feature_columns=["key", "embeddings_name0"],
    label_column="labels",
    num_reducers=2,
    seed=23,
    mesh=mesh,
    queue_name="q-mpjax",
)

ds.set_epoch(0)
batches = list(ds)
# Lockstep: every global-array computation is collective across the two
# processes, so both must run the same number of steps.
counts = multihost_utils.process_allgather(
    jnp.asarray([len(batches)], jnp.int32)
).reshape(-1)
steps = int(counts.min())
assert steps >= 1, f"rank {rank}: no common steps ({list(counts)})"

mean_fn = jax.jit(lambda feats, label: jnp.mean(label))
local_keys = []
global_batch_ok = True
for features, label in batches[:steps]:
    key_arr = features["key"]
    # Global batch spans both processes' shards.
    if key_arr.shape[0] != 2 * batch_size:
        global_batch_ok = False
    # The jitted reduction over a pod-sharded array is the collective.
    m = float(mean_fn(features, label))
    assert np.isfinite(m)
    for shard in key_arr.addressable_shards:
        local_keys.extend(np.asarray(shard.data).reshape(-1).tolist())

with open(f"{rdv}/keys_{rank}.tmp", "w") as f:
    json.dump(
        {"keys": local_keys, "batches": len(batches),
         "steps": steps, "global_batch_ok": global_batch_ok},
        f,
    )
os.rename(f"{rdv}/keys_{rank}.tmp", f"{rdv}/keys_{rank}")
# Drain remaining batches' acks happen inside the iterator already
# (list(ds) consumed everything); rank 0 owns the session shutdown.
multihost_utils.sync_global_devices("done")
runtime.shutdown()
print("MPJAX_RANK_DONE", rank, flush=True)
"""


_PACKED_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["RSDL_T_REPO"])

import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["RSDL_T_COORD"],
    num_processes=2,
    process_id=int(os.environ["RSDL_T_RANK"]),
)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data

rank = int(os.environ["RSDL_T_RANK"])
rdv = os.environ["RSDL_T_RDV"]

# Count global-array assemblies: the packed path must make exactly ONE
# per batch per process; the per-column path pays one per column + label.
counter = {"n": 0}
_orig_assemble = jax.make_array_from_process_local_data
def _counting(*a, **k):
    counter["n"] += 1
    return _orig_assemble(*a, **k)
jax.make_array_from_process_local_data = _counting

if rank == 0:
    ctx = runtime.init(num_workers=2)
    filenames, _ = generate_data(4000, 2, 1, 0.0, rdv + "/data")
    with open(rdv + "/runtime_dir.tmp", "w") as f:
        f.write(ctx.runtime_dir)
    os.rename(rdv + "/runtime_dir.tmp", rdv + "/runtime_dir")
else:
    deadline = time.time() + 120
    while not os.path.exists(rdv + "/runtime_dir"):
        assert time.time() < deadline
        time.sleep(0.2)
    with open(rdv + "/runtime_dir") as f:
        runtime.init(address=f.read().strip(), num_workers=2)
    filenames = sorted(
        os.path.join(rdv, "data", f) for f in os.listdir(rdv + "/data")
    )

mesh = Mesh(np.array(jax.devices()), ("data",))

def run(queue_name, force_percol):
    ds = JaxShufflingDataset(
        filenames,
        num_epochs=1,
        num_trainers=2,
        batch_size=500,
        rank=rank,
        feature_columns=["key", "embeddings_name0"],
        label_column="labels",
        # A declared shape makes a column unpackable, which is what sends
        # a batch down the per-column path; rows are compared flattened.
        feature_shapes=[(1,), (1,)] if force_percol else None,
        label_shape=(1,) if force_percol else None,
        num_reducers=2,
        seed=7,
        mesh=mesh,
        queue_name=queue_name,
    )
    ds.set_epoch(0)
    before = counter["n"]
    rows = []
    nb = 0
    for features, label in ds:
        nb += 1
        for arr in (features["key"], features["embeddings_name0"], label):
            for shard in arr.addressable_shards:
                rows.append(np.asarray(shard.data).reshape(-1).tolist())
    return nb, counter["n"] - before, rows

nb_packed, calls_packed, rows_packed = run("q-mp-packed", False)
nb_col, calls_col, rows_col = run("q-mp-percol", True)

assert nb_packed == nb_col, (nb_packed, nb_col)
# One assembly per batch (packed) vs one per column+label (per-column).
assert calls_packed == nb_packed, (calls_packed, nb_packed)
assert calls_col == 3 * nb_col, (calls_col, nb_col)
# Same seed => identical delivery; the two staging paths must be
# bit-identical shard by shard.
assert rows_packed == rows_col
from jax.experimental import multihost_utils
multihost_utils.sync_global_devices("done")
runtime.shutdown()
print("MPPACK_RANK_DONE", rank, flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_global_array_delivery(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    logs = []
    for rank in range(2):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            RSDL_T_REPO=_REPO,
            RSDL_T_COORD=coord,
            RSDL_T_RANK=str(rank),
            RSDL_T_RDV=str(tmp_path),
        )
        log = tmp_path / f"rank{rank}.log"
        logs.append(log)
        lf = open(log, "w")
        procs.append(
            (
                subprocess.Popen(
                    [sys.executable, "-u", "-c", _WORKER],
                    stdout=lf,
                    stderr=subprocess.STDOUT,
                    env=env,
                ),
                lf,
            )
        )
    try:
        for proc, _ in procs:
            proc.wait(timeout=420)
    finally:
        for proc, lf in procs:
            proc.kill()
            proc.wait()
            lf.close()
    outputs = [log.read_text() for log in logs]
    for rank, out in enumerate(outputs):
        assert f"MPJAX_RANK_DONE {rank}" in out, (
            f"rank{rank} log:\n{out[-4000:]}\n--- other rank:\n"
            f"{outputs[1 - rank][-4000:]}"
        )
    results = [
        json.load(open(tmp_path / f"keys_{rank}")) for rank in range(2)
    ]
    assert all(r["global_batch_ok"] for r in results)
    # Each process saw only its own addressable shard (its trainer rank's
    # rows): across processes the key sets must be disjoint and every key
    # delivered at most once (tails past the common step count excluded).
    k0, k1 = set(results[0]["keys"]), set(results[1]["keys"])
    assert len(k0) == len(results[0]["keys"])  # no dup within rank 0
    assert len(k1) == len(results[1]["keys"])
    assert not (k0 & k1), f"{len(k0 & k1)} keys delivered to both ranks"
    assert (k0 | k1) <= set(range(8000))
    # Substantially all rows arrive (only sub-batch_size tails may drop).
    assert len(k0 | k1) >= 8000 - 2 * 500


def test_two_process_packed_staging(tmp_path):
    """Packed single-transfer staging on a multi-controller pod: one
    global-array assembly per batch per process (vs one per column+label
    on the per-column path), bit-identical batches either way; the
    shard_map unpack launches at independent per-rank rates without a
    cross-host rendezvous."""
    coord = f"127.0.0.1:{_free_port()}"
    procs, logs = [], []
    for rank in range(2):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            RSDL_T_REPO=_REPO,
            RSDL_T_COORD=coord,
            RSDL_T_RANK=str(rank),
            RSDL_T_RDV=str(tmp_path),
        )
        log = tmp_path / f"rank{rank}.log"
        logs.append(log)
        lf = open(log, "w")
        procs.append(
            (
                subprocess.Popen(
                    [sys.executable, "-u", "-c", _PACKED_WORKER],
                    stdout=lf,
                    stderr=subprocess.STDOUT,
                    env=env,
                ),
                lf,
            )
        )
    try:
        for proc, _ in procs:
            proc.wait(timeout=420)
    finally:
        for proc, lf in procs:
            proc.kill()
            proc.wait()
            lf.close()
    outputs = [log.read_text() for log in logs]
    for rank, out in enumerate(outputs):
        assert f"MPPACK_RANK_DONE {rank}" in out, (
            f"rank{rank} log:\n{out[-4000:]}\n--- other rank:\n"
            f"{outputs[1 - rank][-4000:]}"
        )
