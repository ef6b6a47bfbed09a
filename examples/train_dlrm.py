"""End-to-end distributed training example: DLRM over per-epoch-shuffled data.

The TPU-native counterpart of the reference's Horovod example
(``examples/horovod/ray_torch_shuffle.py:39-347``): generate (or reuse) the
synthetic DATA_SPEC dataset, shuffle it every epoch, and train a
data-parallel model on the shuffled batches, measuring per-batch wait times
(the trainer-stall north-star metric, reference ``:195-231``).

Differences by design, not omission:

* One process drives *all local TPU chips* through a ``('data', 'model')``
  mesh — the per-GPU-process + Horovod topology collapses into JAX SPMD.
  Gradient exchange is the ``psum`` XLA inserts for the sharded train step
  (reference uses ``hvd.DistributedOptimizer`` over NCCL, ``:183-193``).
  Multi-host pods: run one copy per host under ``jax.distributed`` — the
  dataset then stages each host's shard and batches are globally sharded.
* The train step is REAL (forward/backward/update on the flagship DLRM);
  the reference mocks it with ``time.sleep`` (``:214``). Pass
  ``--mock-train-step-time`` to reproduce the reference's loader-only
  measurement mode.

Run (CPU smoke): JAX_PLATFORMS=cpu python examples/train_dlrm.py \
    --num-rows 100000 --num-files 4 --batch-size 4096 --epochs 2
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    # Workload (reference arg names where they exist, :39-121).
    p.add_argument("--num-rows", type=int, default=10 ** 6)
    p.add_argument("--num-files", type=int, default=10)
    p.add_argument("--num-row-groups-per-file", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=250_000)
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--num-reducers", type=int, default=8)
    p.add_argument("--max-concurrent-epochs", type=int, default=2)
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--data-dir", type=str, default="example_data")
    p.add_argument(
        "--mock-train-step-time",
        type=float,
        default=None,
        help="Replace the real train step with a sleep of this many seconds "
        "(the reference's default mode, ray_torch_shuffle.py:214).",
    )
    # Model / optimization.
    p.add_argument(
        "--model",
        choices=("dlrm", "transformer"),
        default="dlrm",
        help="Model family: the flagship DLRM or the TabTransformer "
        "encoder (models/transformer.py).",
    )
    p.add_argument("--embed-dim", type=int, default=32)
    p.add_argument("--learning-rate", type=float, default=1e-3)
    p.add_argument(
        "--model-parallelism",
        type=int,
        default=1,
        help="Size of the mesh 'model' axis (shards large embedding vocabs).",
    )
    # Checkpoint / resume (no reference analog — the loader had none,
    # SURVEY §5; preemptible TPU pods need it).
    p.add_argument(
        "--checkpoint-dir",
        type=str,
        default=None,
        help="Enable checkpointing to this directory; if it already holds a "
        "checkpoint, training resumes from it (mid-epoch batch cursor "
        "included).",
    )
    p.add_argument(
        "--checkpoint-every",
        type=int,
        default=50,
        help="Steps between checkpoints.",
    )
    p.add_argument(
        "--loader",
        choices=("auto", "resident", "mapreduce"),
        default="auto",
        help="Batch delivery path: 'resident' shuffles each epoch on "
        "device (permutation + gather in HBM; needs the packed dataset "
        "to fit the device budget), 'mapreduce' is the general host "
        "pipeline, 'auto' picks resident when it fits.",
    )
    # Gradient plane (reference: Horovod op=Average/Adasum + fp16
    # compression flags, ray_torch_shuffle.py:183-193).
    p.add_argument(
        "--grad-reduce",
        choices=("pjit", "mean", "adasum"),
        default="pjit",
        help="'pjit' (default): sharding-driven step, XLA derives the "
        "all-reduce. 'mean'/'adasum': the explicit shard_map step with a "
        "hand-written collective — 'adasum' is the hvd.Adasum analog "
        "(adaptive summation). Both need --model-parallelism 1 "
        "(replicated params).",
    )
    p.add_argument(
        "--grad-bf16",
        action="store_true",
        help="bf16 gradient wire compression (the fp16-compression "
        "analog; explicit --grad-reduce modes only).",
    )
    p.add_argument(
        "--smoke",
        action="store_true",
        help="Tiny CI workload preset (overrides the size knobs).",
    )
    args = p.parse_args(argv)
    if args.grad_reduce != "pjit" and args.model_parallelism != 1:
        p.error("--grad-reduce mean/adasum requires --model-parallelism 1")
    if args.grad_bf16 and args.grad_reduce == "pjit":
        p.error("--grad-bf16 needs an explicit mode (--grad-reduce mean/adasum)")
    if args.smoke:
        args.num_rows = 50_000
        args.num_files = 4
        args.num_row_groups_per_file = 1
        args.batch_size = 4096
        args.epochs = 2
        args.num_reducers = 4
        args.embed_dim = 8
        args.data_dir = os.path.join(args.data_dir, "smoke")
    return args


def get_data(args):
    """Generate the dataset once and reuse it across runs (the reference
    caches the filename list in a pickle, ``ray_torch_shuffle.py:294-314``)."""
    from ray_shuffling_data_loader_tpu.data_generation import (
        cached_generate_data,
    )

    t0 = time.perf_counter()
    filenames, num_bytes = cached_generate_data(
        args.num_rows,
        args.num_files,
        args.num_row_groups_per_file,
        args.data_dir,
        seed=args.seed,
    )
    if time.perf_counter() - t0 > 1.0:
        print(f"Generated {num_bytes / 1e9:.2f} GB.")
    else:
        print(f"Reusing {len(filenames)} cached files in {args.data_dir}")
    return filenames


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax

    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.data_generation import (
        DATA_SPEC,
        LABEL_COLUMN,
    )
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
    from ray_shuffling_data_loader_tpu.parallel import (
        batch_sharding,
        init_state,
        make_train_step,
    )
    from ray_shuffling_data_loader_tpu.parallel.mesh import make_mesh

    from ray_shuffling_data_loader_tpu import resident as resident_mod

    runtime.init()
    os.makedirs(args.data_dir, exist_ok=True)
    filenames = get_data(args)

    # Mesh over every local chip: batch along 'data', big vocabs along
    # 'model' (the Horovod example instead pins one GPU per worker process,
    # ray_torch_shuffle.py:144-151).
    mesh = make_mesh(model_parallelism=args.model_parallelism)
    print(f"mesh: {dict(mesh.shape)} on {jax.device_count()} devices")

    feature_columns = [c for c in DATA_SPEC if c != LABEL_COLUMN]

    # Loader choice (see resident.py): epoch shuffle on device when the
    # packed dataset fits the budget, host map/reduce otherwise.
    if args.loader == "mapreduce":
        use_resident = False
    else:
        # SPMD on pods: every process evaluates this same call, so the
        # pod-consistent vote is safe (resident engages only when every
        # host's budget agrees).
        fits = resident_mod.fits_device(
            filenames,
            len(feature_columns),
            mesh=mesh,
            num_rows=args.num_rows,
            pod_consistent=True,
        )
        use_resident = args.loader == "resident" or fits
        if use_resident and not fits:
            # Say WHY auto would have declined, so the one warning that
            # matters (a genuine budget overrun on a real accelerator)
            # isn't drowned by deliberate CPU/pod opt-ins.
            if jax.process_count() > 1:
                print(
                    "note: pod auto-select declined (some host's budget "
                    "vote was no); every process is forcing resident"
                )
            elif jax.local_devices()[0].platform == "cpu":
                print(
                    "note: resident loader forced on the CPU backend "
                    "(auto prefers map/reduce there)"
                )
            else:
                print(
                    "warning: --loader resident forced but the packed "
                    "dataset may exceed the device memory budget"
                )
    print(f"loader: {'device-resident' if use_resident else 'map/reduce'}")

    if args.model == "transformer":
        from ray_shuffling_data_loader_tpu.models import (
            transformer_for_data_spec,
        )

        model = transformer_for_data_spec(embed_dim=args.embed_dim)
    else:
        model = dlrm_for_data_spec(embed_dim=args.embed_dim)
    optimizer = optax.adam(args.learning_rate)
    example = {
        c: jnp.zeros((args.batch_size,), jnp.int32) for c in feature_columns
    }
    state, state_shardings = init_state(model, optimizer, mesh, example)
    if args.grad_reduce == "pjit":
        train_step = make_train_step(model, optimizer, mesh, state_shardings)
    else:
        # Explicit gradient plane (replicated params): hand-written
        # pmean or Adasum collective under shard_map — the literal
        # Horovod-allreduce analog, selectable like the reference's
        # op=Average/Adasum flag (ray_torch_shuffle.py:183-193).
        from ray_shuffling_data_loader_tpu.parallel import (
            make_psum_train_step,
        )

        train_step = make_psum_train_step(
            model,
            optimizer,
            mesh,
            grad_dtype=jnp.bfloat16 if args.grad_bf16 else None,
            grad_reduce=args.grad_reduce,
        )
        print(
            f"gradient plane: explicit {args.grad_reduce}"
            + (" + bf16 wire" if args.grad_bf16 else "")
        )

    # Compile off the hot path, with inputs placed exactly as real batches
    # will arrive (committed + mesh-sharded). AOT lower/compile: no
    # execution, so the donated state buffer stays live for the loop.
    bsh = batch_sharding(mesh, 1)
    warm_feats = {k: jax.device_put(v, bsh) for k, v in example.items()}
    warm_labels = jax.device_put(
        jnp.zeros((args.batch_size,), jnp.float32), bsh
    )
    train_step = train_step.lower(state, warm_feats, warm_labels).compile()

    # Checkpoint/resume: restore state + batch cursor if a checkpoint
    # exists, and save every --checkpoint-every steps.
    ckpt_mgr = None
    start_epoch, resume_skip, global_step = 0, 0, 0
    stream_config = None
    if args.checkpoint_dir:
        from ray_shuffling_data_loader_tpu import BatchCursor, CheckpointManager

        ckpt_mgr = CheckpointManager(args.checkpoint_dir)
        stream_config = BatchCursor.stream_config(
            seed=args.seed,
            batch_size=args.batch_size,
            num_trainers=1,
            num_reducers=args.num_reducers,
            num_files=len(filenames),
            drop_last=True,
        )
        restored, cursor = ckpt_mgr.restore(
            target=state, shardings=state_shardings
        )
        if cursor is not None:
            # The two loaders produce different (both deterministic)
            # batch streams, so a resume must keep the loader the
            # checkpoint was written under. Cursors from before the
            # resident loader existed carry no key and mean map/reduce.
            ckpt_loader = (cursor.config or {}).get("loader", "mapreduce")
            if args.loader not in ("auto", ckpt_loader):
                raise SystemExit(
                    f"--loader {args.loader} conflicts with this "
                    f"checkpoint's batch stream (written under "
                    f"{ckpt_loader}); resume with --loader {ckpt_loader}"
                )
            if use_resident != (ckpt_loader == "resident"):
                print(
                    f"checkpoint forces loader {ckpt_loader} (overriding "
                    f"the auto choice above); if this machine cannot fit "
                    f"the resident buffer, restart with a fresh "
                    f"--checkpoint-dir"
                )
            use_resident = ckpt_loader == "resident"
            if "loader" in (cursor.config or {}):
                stream_config["loader"] = ckpt_loader
            cursor.validate(stream_config)
            state = restored if restored is not None else state
            start_epoch = cursor.epoch
            resume_skip = cursor.batches_yielded
            global_step = cursor.step
            print(
                f"resuming from step {global_step}: epoch {start_epoch}, "
                f"skipping {resume_skip} already-trained batches"
            )
        else:
            stream_config["loader"] = (
                "resident" if use_resident else "mapreduce"
            )

    if use_resident:
        ds = resident_mod.DeviceResidentShufflingDataset(
            filenames,
            num_epochs=args.epochs,
            batch_size=args.batch_size,
            feature_columns=feature_columns,
            label_column=LABEL_COLUMN,
            seed=args.seed,
            mesh=mesh,
            num_rows=args.num_rows,
        )
    else:
        ds = JaxShufflingDataset(
            filenames,
            num_epochs=args.epochs,
            num_trainers=1,
            batch_size=args.batch_size,
            rank=0,
            feature_columns=feature_columns,
            label_column=LABEL_COLUMN,
            num_reducers=args.num_reducers,
            max_concurrent_epochs=args.max_concurrent_epochs,
            seed=args.seed,
            mesh=mesh,
            start_epoch=start_epoch,
        )

    # Train loop with per-batch wait-time measurement (reference ``_train``,
    # ray_torch_shuffle.py:195-231).
    all_wait_times = []
    loss = float("nan")
    for epoch in range(start_epoch, args.epochs):
        skip = resume_skip if epoch == start_epoch else 0
        ds.set_epoch(epoch, skip_batches=skip)
        epoch_start = time.perf_counter()
        wait_times = []
        num_batches = skip
        last_done = time.perf_counter()
        for features, labels in ds:
            wait_times.append(time.perf_counter() - last_done)
            if args.mock_train_step_time is not None:
                time.sleep(args.mock_train_step_time)
            else:
                state, metrics = train_step(state, features, labels)
                jax.block_until_ready(state.step)
                loss = float(metrics["loss"])
            num_batches += 1
            global_step += 1
            if ckpt_mgr is not None and global_step % args.checkpoint_every == 0:
                from ray_shuffling_data_loader_tpu import BatchCursor

                ckpt_mgr.save(
                    global_step,
                    cursor=BatchCursor(
                        epoch=epoch,
                        batches_yielded=num_batches,
                        config=stream_config,
                    ),
                    state=state,
                )
            last_done = time.perf_counter()
        epoch_s = time.perf_counter() - epoch_start
        all_wait_times.extend(wait_times)
        if not wait_times:
            print(
                f"epoch {epoch}: 0 batches — batch_size ({args.batch_size}) "
                f"exceeds the rows available per trainer and drop_last "
                f"discarded the partial tail"
            )
            continue
        wt = np.asarray(wait_times)
        print(
            f"epoch {epoch}: {num_batches} batches in {epoch_s:.2f}s, "
            f"loss={loss:.4f}, batch wait mean={wt.mean():.4f}s "
            f"std={wt.std():.4f} max={wt.max():.4f} min={wt.min():.4f}"
        )

    if not all_wait_times:
        print("no batches were delivered; nothing to summarize")
        return 1
    wt = np.asarray(all_wait_times)
    staging = ds.stats.as_dict()
    print(
        f"total: {len(all_wait_times)} batches; batch wait "
        f"mean={wt.mean():.4f}s std={wt.std():.4f} max={wt.max():.4f} "
        f"min={wt.min():.4f}"
    )
    print(
        f"staging: {staging['bytes_staged'] / 1e9:.3f} GB to HBM, "
        f"stall {staging['stall_s']:.3f}s over {staging['stalls']} stalls"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
