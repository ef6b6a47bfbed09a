"""Pod training recipe: DLRM over pod-global batches under ``jax.distributed``.

The missing piece the round-1 review called out: an *example-side* recipe
for running the trainer across TPU-VM hosts (the reference's analog is the
Horovod-over-Ray driver, ``/root/reference/examples/horovod/
ray_torch_shuffle.py:319-344``, which `RayExecutor` fans out one process
per GPU). On a TPU pod the topology is one process per host:

1. every host runs THIS script (gcloud ``--worker=all``, see
   ``benchmarks/launch_tpu_pod.sh``);
2. ``jax.distributed.initialize()`` discovers the pod (no args needed on
   Cloud TPU) and gives each process its ``process_index``;
3. process 0 starts the shuffle runtime cluster (head) and kicks off the
   shuffle; other hosts join over DCN via the published address file on
   the shared filesystem (or ``--cluster-address``);
4. each host consumes its rank's shard through ``JaxShufflingDataset``,
   which assembles **pod-global arrays** via
   ``jax.make_array_from_process_local_data`` over a global ``('data',)``
   mesh — the jitted train step then runs SPMD across the whole pod, with
   gradient ``psum`` riding the ICI (no NCCL, no parameter server).

Single-host smoke (2 simulated processes, CPU):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/train_dlrm_pod.py --simulate-pod 2

Real pod (v5e-16, 4 hosts): see benchmarks/launch_tpu_pod.sh, which runs
this script on every worker with a shared --rendezvous-dir.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-rows", type=int, default=200_000)
    p.add_argument("--num-files", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=8_192)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--num-reducers", type=int, default=8)
    p.add_argument("--embed-dim", type=int, default=16)
    p.add_argument("--vocab-cap", type=int, default=1000)
    p.add_argument("--seed", type=int, default=29)
    p.add_argument(
        "--rendezvous-dir",
        type=str,
        default="pod_rendezvous",
        help="Shared dir (NFS/GCS-fuse on a real pod) for the runtime "
        "cluster address + data paths.",
    )
    p.add_argument(
        "--coordinator",
        type=str,
        default=None,
        help="host:port for jax.distributed on non-Cloud-TPU setups "
        "(Cloud TPU pods auto-discover with no args).",
    )
    p.add_argument(
        "--num-processes",
        type=int,
        default=None,
        help="With --coordinator: total process count.",
    )
    p.add_argument(
        "--process-id", type=int, default=None, help=argparse.SUPPRESS
    )
    p.add_argument(
        "--simulate-pod",
        type=int,
        default=None,
        metavar="N",
        help="Launch N local processes with a local coordinator (CPU "
        "smoke of the full pod flow).",
    )
    p.add_argument(
        "--loader",
        choices=("mapreduce", "resident"),
        default="mapreduce",
        help="'resident' stages each host's addressable row range into "
        "device memory once and shuffles every epoch on device (needs "
        "the packed dataset to fit the pod's HBM; see resident.py).",
    )
    return p.parse_args(argv)


def train_main(args) -> int:
    import jax

    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    enable_compile_cache()

    # 1. Pod discovery. On Cloud TPU, initialize() needs no arguments.
    if args.coordinator:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    else:
        jax.distributed.initialize()
    rank = jax.process_index()
    world = jax.process_count()

    import numpy as np
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.data_generation import (
        DATA_SPEC,
        LABEL_COLUMN,
        cached_generate_data,
    )
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
    from ray_shuffling_data_loader_tpu.parallel import (
        init_state,
        make_train_step,
    )

    rdv = args.rendezvous_dir
    os.makedirs(rdv, exist_ok=True)
    # A persistent rendezvous dir may hold a PREVIOUS run's address file;
    # ranks that matched on a bare filename could join a dead head. Scope
    # the filename to THIS run with a nonce agreed over jax.distributed
    # (broadcast from process 0) — stale files can never match it.
    if world > 1:
        from jax.experimental import multihost_utils

        nonce = int(
            multihost_utils.broadcast_one_to_all(
                jnp.asarray(np.random.randint(0, 2**31), jnp.int32)
            )
        )
        addr_file = os.path.join(rdv, f"cluster_address_{nonce}")
    else:
        addr_file = os.path.join(rdv, "cluster_address")

    # 2. Shuffle-runtime topology mirrors the pod: host 0 is the cluster
    #    head, everyone else joins over DCN.
    if rank == 0:
        ctx = (
            runtime.init_cluster(num_workers=4)
            if world > 1
            else runtime.init(num_workers=4)
        )
        filenames, num_bytes = cached_generate_data(
            args.num_rows,
            args.num_files,
            2,
            os.path.join(rdv, "data"),
            seed=args.seed,
        )
        if world > 1:
            with open(addr_file + ".tmp", "w") as f:
                f.write(ctx.cluster.address)
            os.rename(addr_file + ".tmp", addr_file)
        print(
            f"[pod] rank 0: cluster up, {num_bytes/1e9:.2f} GB over "
            f"{len(filenames)} files",
            flush=True,
        )
    else:
        deadline = time.time() + 300
        while not os.path.exists(addr_file):
            if time.time() > deadline:
                raise TimeoutError("rank 0 never published the cluster address")
            time.sleep(0.5)
        with open(addr_file) as f:
            runtime.init(address=f.read().strip(), num_workers=4)
        filenames = sorted(
            os.path.join(rdv, "data", name)
            for name in os.listdir(os.path.join(rdv, "data"))
            if name.endswith(".snappy")
        )

    # Canonical file order on EVERY rank: rank 0 holds the generator's
    # numeric-order list, other ranks listdir'd lexicographically — the
    # resident loader maps row offsets from this order, so divergence
    # would silently corrupt the global buffer (mapreduce is order-
    # insensitive, but one canonical order costs nothing).
    filenames = sorted(filenames)

    # 3. Pod-global mesh over EVERY device in the pod; batches assemble as
    #    global arrays, so the train step is one SPMD program.
    mesh = Mesh(np.array(jax.devices()), ("data",))
    feature_columns = [c for c in DATA_SPEC if c != LABEL_COLUMN]
    model = dlrm_for_data_spec(
        embed_dim=args.embed_dim, vocab_cap=args.vocab_cap
    )
    optimizer = optax.adam(1e-3)
    example = {
        c: jnp.zeros((args.batch_size,), jnp.int32) for c in feature_columns
    }
    state, shardings = init_state(model, optimizer, mesh, example)
    step_fn = make_train_step(model, optimizer, mesh, shardings)

    if args.loader == "resident":
        from ray_shuffling_data_loader_tpu.resident import (
            DeviceResidentShufflingDataset,
        )

        # Every process stages its addressable row range; the buffer
        # spans the pod and epoch shuffles are SPMD device gathers.
        ds = DeviceResidentShufflingDataset(
            filenames,
            num_epochs=args.epochs,
            batch_size=args.batch_size,
            feature_columns=feature_columns,
            label_column=LABEL_COLUMN,
            seed=args.seed,
            mesh=mesh,
        )
    else:
        ds = JaxShufflingDataset(
            filenames,
            num_epochs=args.epochs,
            num_trainers=world,
            batch_size=args.batch_size,
            rank=rank,
            feature_columns=feature_columns,
            label_column=LABEL_COLUMN,
            num_reducers=args.num_reducers,
            seed=args.seed,
            mesh=mesh,
            queue_name="pod-queue",
        )

    # 4. Train. Every process steps in lockstep on its shard of the global
    #    batch; collectives ride ICI. Ranks can receive different batch
    #    counts (reducer outputs split by rank), and the jitted step is
    #    collective — so each step is gated on an all-ranks-have-a-batch
    #    sync. Batches STREAM through the prefetch ring (materializing a
    #    whole epoch of device-resident batches would blow the HBM budget
    #    on a real pod workload and serialize all H2D staging).
    from jax.experimental import multihost_utils

    def _all_have_next(batch) -> bool:
        flags = multihost_utils.process_allgather(
            jnp.asarray([0 if batch is None else 1], jnp.int32)
        ).reshape(-1)
        return int(flags.min()) == 1

    steps_done = 0
    loss = float("nan")
    for epoch in range(args.epochs):
        ds.set_epoch(epoch)
        it = iter(ds)
        steps = 0
        batch = next(it, None)
        while _all_have_next(batch):
            features, label = batch
            state, metrics = step_fn(state, features, label)
            steps += 1
            steps_done += 1
            batch = next(it, None)
        if steps:
            loss = float(metrics["loss"])
        # Drain any leftover (dropped) batches so their task_done acks
        # release the epoch window for the next epoch.
        while batch is not None:
            batch = next(it, None)
        print(
            f"[pod] rank {rank}: epoch {epoch} done, "
            f"{steps} steps, loss {loss:.4f}",
            flush=True,
        )
    multihost_utils.sync_global_devices("train-done")
    stats = ds.stats.as_dict()
    print(
        f"[pod] rank {rank}: {steps_done} steps total, "
        f"{stats['bytes_staged']/1e9:.3f} GB staged, "
        f"stall {stats['stall_s']:.2f}s",
        flush=True,
    )
    runtime.shutdown()
    return 0


def simulate_pod(args) -> int:
    """Run the full pod flow as N local processes (CPU smoke)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for pid in range(args.simulate_pod):
        cmd = [
            sys.executable,
            os.path.abspath(__file__),
            "--coordinator",
            f"127.0.0.1:{port}",
            "--num-processes",
            str(args.simulate_pod),
            "--process-id",
            str(pid),
            "--rendezvous-dir",
            args.rendezvous_dir,
            "--num-rows",
            str(args.num_rows),
            "--batch-size",
            str(args.batch_size),
            "--epochs",
            str(args.epochs),
            "--loader",
            args.loader,
        ]
        # The simulated pod is a CPU smoke: N processes cannot share the
        # chips of one host. This launcher itself stays off JAX.
        env = dict(
            os.environ, RSDL_ADVERTISE_HOST="127.0.0.1", JAX_PLATFORMS="cpu"
        )
        procs.append(subprocess.Popen(cmd, env=env))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


if __name__ == "__main__":
    _args = parse_args()
    if _args.simulate_pod:
        sys.exit(simulate_pod(_args))
    sys.exit(train_main(_args))
