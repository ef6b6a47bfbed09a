"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

drives the main path once, in one process, on every local TPU device:
``generate_data`` -> ``runtime.init`` -> ``JaxShufflingDataset`` (map/reduce
shuffle, device-direct staging, jitted unpack) -> ``init_state`` /
``make_train_step`` on ``dlrm_for_data_spec()`` at its shipped width (full
``DATA_SPEC`` vocabularies, ``embed_dim=32``, top MLP 256-128-64, Adam,
weights from a seed), then the same step fed by
``DeviceResidentShufflingDataset``, per batch and through
``make_fused_epoch``. Every phase raises on failure, so the exit code is
the result. The last line of standard output is one JSON object naming the
device as JAX reports it. Times printed on the way are smoke timings on
that device, not metrics.

Without a TPU it exits non-zero before doing any work and prints no
result. ``--rehearse-on-cpu`` walks the same phases at a toy size with the
kernels in the Pallas interpreter, to debug the script itself; every line
it prints says so and it prints no result either.

``--trace-out <file>`` runs the streaming epochs and the resident loader's
per-batch epochs under a JAX profiler session with ``RSDL_TRACE`` on in
every process, and writes ONE Chrome trace (open it at
https://ui.perfetto.dev): the spans of the driver, the pool workers and
the actors, and the device's programs and operations, all on the wall
clock (``telemetry.trace_export(path, xplane=...)``). It then checks the
order that file has to show: an epoch's first ``reduce`` ends before its
first ``stage:h2d`` begins, every batch's ``stage:transfer`` ends before
the ``jit_step_fn`` that consumes it does, and a device operation of the
step carries the ``op_name`` the step's ``step:ops`` table gives it.

All ``jax`` imports sit inside ``main()``: ``runtime.init()`` spawns
workers that re-import ``__main__``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

SEED = 0
EPOCHS = 2
# Shipped sizes on the chip; a toy on the CPU rehearsal.
CHIP = dict(
    rows=2_000_000, files=8, batch=250_000, reducers=4, kernel_batch=4096
)
TOY = dict(rows=32_768, files=4, batch=4_096, reducers=2, kernel_batch=64)
HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, "smoke_data", "chip_smoke")


def check_merged_trace(path: str, say) -> None:
    """The order one clock has to show, read back from the merged file."""
    with open(path) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]

    def named(name):
        """The program's own spans of that name (the xplane's host plane
        holds the live ones a second time, without their args)."""
        return sorted(
            (
                e for e in events
                if e["name"] == name and e.get("cat") != "xplane"
            ),
            key=lambda e: e["ts"],
        )

    def end(e):
        return e["ts"] + e["dur"]

    reduces, staged = named("reduce"), named("stage:h2d")
    assert reduces and staged, (len(reduces), len(staged))
    assert len({e["pid"] for e in reduces} - {os.getpid()}) >= 1, (
        "no reduce span from a pool worker"
    )
    for epoch in sorted({e["args"]["epoch"] for e in staged}):
        first_reduce = min(
            end(e) for e in reduces if e["args"].get("epoch") == epoch
        )
        first_h2d = min(
            e["ts"] for e in staged if e["args"]["epoch"] == epoch
        )
        assert first_reduce <= first_h2d, (epoch, first_reduce, first_h2d)
    transfers = sorted(
        named("stage:transfer"),
        key=lambda e: (e["args"]["epoch"], e["args"]["batch"]),
    )
    assert len(transfers) == len(staged), (len(transfers), len(staged))
    steps = [
        e for e in events
        if e.get("cat") == "xplane" and e["name"].startswith("jit_step_fn")
    ]
    steps.sort(key=lambda e: e["ts"])
    say(
        f"  merged trace {path}: {len(events)} spans from "
        f"{len({e['pid'] for e in events})} processes and planes, "
        f"{len(reduces)} reduce, {len(transfers)} stage:transfer, "
        f"{len(steps)} jit_step_fn"
    )
    if not steps:
        # The CPU rehearsal's xplane has no device plane.
        say("  no device programs in the trace: step order not checked")
        return
    assert len(steps) >= len(transfers), (len(steps), len(transfers))
    for transfer, step in zip(transfers, steps):
        assert end(transfer) <= end(step), (transfer, step)
    say(
        "  one clock: every epoch's first reduce ended before its first "
        "stage:h2d began, every stage:transfer before its step ended"
    )
    # The step said what is in the program it compiled (``step:ops``), so
    # a device operation shows its scope.
    scoped = [
        e for e in events
        if e.get("cat") == "xplane" and e["args"].get("op_name")
    ]
    assert scoped, "no device operation carries its op_name"
    say(
        f"  {len(scoped)} device operations carry their op_name: "
        f"{scoped[0]['name']} is {scoped[0]['args']['op_name']}"
    )


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument(
        "--trace-out", default=None,
        help="write one merged Chrome trace of spans and device operations",
    )
    args = ap.parse_args(argv)
    rehearse = args.rehearse_on_cpu
    tag = "[chip_smoke]"
    if rehearse:
        tag = "[chip_smoke CPU REHEARSAL - not a chip run]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    t_start = time.perf_counter()
    import jax

    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"device: {json.dumps(device)}")
    wanted = "cpu" if rehearse else "tpu"
    if device["platform"] != wanted:
        why = "the rehearsal wants the CPU" if rehearse else "no TPU"
        print(
            f"{tag} FAILED: platform is {device['platform']!r}, {why}",
            file=sys.stderr,
        )
        return 1
    size = TOY if rehearse else CHIP
    n_dev = len(devices)
    num_rows, batch = size["rows"], size["batch"]
    if batch % n_dev:
        raise ValueError(f"batch {batch} does not split over {n_dev} devices")

    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_shuffling_data_loader_tpu import native, runtime, telemetry
    from ray_shuffling_data_loader_tpu.data_generation import (
        DATA_SPEC,
        KEY_COLUMN,
        LABEL_COLUMN,
        generate_data,
    )
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
    from ray_shuffling_data_loader_tpu.ops import (
        attention_reference,
        dot_interaction,
        dot_interaction_reference,
        flash_attention,
    )
    from ray_shuffling_data_loader_tpu.parallel import (
        batch_sharding,
        init_state,
        make_mesh,
        make_step_body,
        make_train_step,
    )
    from ray_shuffling_data_loader_tpu.resident import (
        DeviceResidentShufflingDataset,
        make_fused_epoch,
    )

    def cache_entries() -> int:
        if not os.path.isdir(cache_dir):
            return 0
        return sum(
            1 for name in os.listdir(cache_dir) if name.endswith("-cache")
        )

    def bytes_in_use():
        return [
            (d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices
        ]

    entries_before = cache_entries()
    in_use_start = bytes_in_use()
    say(f"compile cache: {cache_dir} ({entries_before} entries)")
    phases = {}

    def phase(name):
        """Run the decorated function now, as the phase ``name``."""

        def wrap(fn):
            t0 = time.perf_counter()
            say(f"phase {name}: start")
            fn()
            phases[name] = round(time.perf_counter() - t0, 1)
            say(f"phase {name}: ok in {phases[name]} s")

        return wrap

    # g++ is part of the installation: a kernel library that did not build
    # is an error here, not a reason to run on numpy.
    assert native.native_available(), "native kernels failed to build"

    # -- kernels ----------------------------------------------------------
    rng = np.random.default_rng(SEED)

    def rel_err(got, want):
        """Largest error as a share of the reference's largest magnitude."""
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape, (got.shape, want.shape)
        assert np.isfinite(got).all()
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    # The kernels take bfloat16 in, accumulate in float32 and round the
    # result to bfloat16 once: 8 significant bits, half an ulp = 2**-9 of
    # the value. The references run in float32 at "highest" matmul
    # precision on the same bfloat16 inputs. Four ulps of the largest
    # magnitude leave room for the order of accumulation and nothing else:
    # accumulating in bfloat16 over 32 or more terms would miss it.
    TOL = 4 * 2.0**-8

    def with_grads(fn, *args, ct):
        """``fn``'s value and its cotangents for ``ct``, in one jit."""

        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out, *vjp(ct.astype(out.dtype)))

        return jax.jit(run)(*args)

    def reference(fn, *args, ct):
        """The same from ``fn`` in float32 at full matmul precision (a
        TPU runs a float32 matmul in bfloat16 passes unless told)."""
        with jax.default_matmul_precision("highest"):
            return with_grads(
                lambda *a: fn(*(x.astype(jnp.float32) for x in a)),
                *args,
                ct=ct,
            )

    def bf16(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    @phase("kernels")
    def _kernels():
        errs = {}
        # DLRM interaction at the model's shape; kernel_batch + 100 rows is
        # not a multiple of the 256-row tile, so the padded tail tile runs.
        rows = size["kernel_batch"] + 100
        x, ct = bf16(rows, 19, 32), bf16(rows, 171)
        got = with_grads(
            lambda x: dot_interaction(x, use_pallas=True, interpret=rehearse),
            x,
            ct=ct,
        )
        want = reference(dot_interaction_reference, x, ct=ct)
        for name, g, w in zip(("fwd", "grad"), got, want):
            errs[f"interaction {name}"] = rel_err(g, w)
        # Flash attention at the head widths the models use; 300 is not a
        # multiple of the 128 block, so padded query and key blocks run. The
        # toy models' blocks of 32 over 64 tokens are under a vreg's lanes.
        for d, t, blocks in ((32, 300, (128, 128)), (128, 300, (128, 128)),
                             (64, 64, (32, 32))):
            for causal in (False, True):
                q, k, v, ct = (bf16(2, t, 2, d) for _ in range(4))
                got = with_grads(
                    lambda q, k, v: flash_attention(
                        q, k, v, causal=causal, use_pallas=True,
                        block_q=blocks[0], block_k=blocks[1],
                        interpret=rehearse,
                    ),
                    q, k, v, ct=ct,
                )
                want = reference(
                    lambda q, k, v: attention_reference(
                        q, k, v, causal=causal
                    ),
                    q, k, v, ct=ct,
                )
                for name, g, w in zip(("fwd", "dQ", "dK", "dV"), got, want):
                    errs[f"flash d={d} t={t} causal={causal} {name}"] = (
                        rel_err(g, w)
                    )
        for name, err in errs.items():
            say(f"  {name}: rel err {err:.2e} (limit {TOL:.2e})")
            assert err < TOL, (name, err)

    # Learned sparse attention at small blocks (query blocks of 256, key
    # blocks of 128: the words' block is 8 sublanes and the key blocks a
    # lane tile), 16 indexer heads of 64 and 8 query heads over 2 of 128,
    # each kernel against its XLA path on the chip.
    @phase("sparse kernels")
    def _sparse_kernels():
        from ray_shuffling_data_loader_tpu.ops import sparse_attention as sa

        t, bq, bk, topk = (128, 32, 16, 24) if rehearse else (512, 256, 128, 96)
        f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
        qi, ki, wi = f32(1, t, 16, 64), f32(1, t, 64), f32(1, t, 16)
        kernel = dict(block_q=bq, block_k=bk, use_pallas=True, interpret=rehearse)
        oracle = dict(block_q=bq, block_k=bk, use_pallas=False)
        words, lse_i = jax.jit(
            lambda *a: sa.index_select(*a, topk, **kernel))(qi, ki, wi)
        want_words, want_lse = jax.jit(
            lambda *a: sa.index_select(*a, topk, **oracle))(qi, ki, wi)
        # Both score in float32 at the highest precision, in other orders:
        # a pair of scores within a rounding of each other at the k-th place
        # may fall either way; more than one pair in 10,000 moved is a fault.
        got_sel, want_sel = (np.asarray(sa.unpack(w)) for w in (words, want_words))
        moved = float(np.mean(got_sel != want_sel)) * t / topk
        say(f"  sparse_index_fwd: {moved:.2e} of the selected pairs moved (limit 1e-4)")
        assert moved <= 1e-4, moved
        assert int(got_sel.sum()) == sa.selected_pairs(t, topk)
        q, k, v, ct = bf16(1, t, 8, 128), bf16(1, t, 2, 128), bf16(1, t, 2, 128), bf16(1, t, 8, 128)
        errs = {}
        got = with_grads(
            lambda q, k, v: flash_attention(q, k, v, causal=True, selected=want_words,
                                            **kernel)[0],
            q, k, v, ct=ct,
        )
        want = reference(
            lambda q, k, v: flash_attention(q, k, v, causal=True, selected=want_words,
                                            **oracle)[0],
            q, k, v, ct=ct,
        )
        for name, g, w in zip(("fwd", "dQ", "dK", "dV"), got, want):
            errs[f"flash_attention_sparse {name}"] = rel_err(g, w)
        _, lse = flash_attention(q, k, v, causal=True, selected=want_words, **oracle)

        def loss(how, lse_i):
            return jax.jit(jax.value_and_grad(
                lambda a, b, c: sa.index_loss(a, b, c, q, k, lse, lse_i, want_words, **how),
                argnums=(0, 1, 2),
            ))(qi, ki, wi)

        (got_l, got_g), (want_l, want_g) = loss(kernel, want_lse), loss(oracle, want_lse)
        errs["sparse_index_bwd loss"] = abs(float(got_l) - float(want_l)) / abs(float(want_l))
        for name, g, w in zip(("dq", "dk", "dw"), got_g, want_g):
            errs[f"sparse_index_bwd {name}"] = rel_err(g, w)
        for name, err in errs.items():
            say(f"  {name}: rel err {err:.2e} (limit {TOL:.2e})")
            assert err < TOL, (name, err)

    # Latent attention's split key at small blocks (query blocks of 256, key
    # blocks of 128 over 512 tokens), 4 heads of 128 + 64 over one shared
    # rotary key of 64 and values of 128: the three flash_attention_latent_*
    # kernels against the XLA path, which repeats the shared part to the
    # heads. TOL holds for all five: dK_shared sums the heads' rows in
    # float32 and is rounded to bfloat16 once, as every other output is.
    @phase("latent kernels")
    def _latent_kernels():
        t, bq, bk = (64, 32, 16) if rehearse else (512, 256, 128)
        q, k, v, ct = bf16(1, t, 4, 192), bf16(1, t, 4, 128), bf16(1, t, 4, 128), bf16(1, t, 4, 128)
        k_rope = bf16(1, t, 1, 64)

        def attend(use_pallas):
            kw = dict(block_q=bq, block_k=bk, interpret=rehearse) if use_pallas else {}
            return lambda q, k, v, kr: flash_attention(
                q, k, v, causal=True, use_pallas=use_pallas, k_shared=kr, **kw
            )

        got = with_grads(attend(True), q, k, v, k_rope, ct=ct)
        want = reference(attend(False), q, k, v, k_rope, ct=ct)
        for name, g, w in zip(("fwd", "dQ", "dK", "dV", "dK_shared"), got, want):
            err = rel_err(g, w)
            say(f"  flash_attention_latent {name}: rel err {err:.2e} (limit {TOL:.2e})")
            assert err < TOL, (name, err)

    # -- data and model -----------------------------------------------------
    model_columns = [c for c in DATA_SPEC if c != LABEL_COLUMN]
    # The key rides along for the exactly-once checks; the model never
    # sees it.
    feature_columns = [*model_columns, KEY_COLUMN]
    mesh = make_mesh()  # every local device on the data axis

    trace_dir = None
    if args.trace_out:
        # Before runtime.init(): the workers and actors inherit the switch
        # and the spool directory through their environment.
        trace_dir = tempfile.mkdtemp(prefix="chip-smoke-trace-")
        telemetry.enable(os.path.join(trace_dir, "spool"))
        telemetry.set_process_name("chip-smoke-driver")
    in_session = False

    def start_session():
        nonlocal in_session
        if trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            in_session = True

    def stop_session():
        nonlocal in_session
        if in_session:
            jax.profiler.stop_trace()
            in_session = False

    runtime.init()
    ctx = runtime.get_context()
    try:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        t0 = time.perf_counter()
        filenames, nbytes = generate_data(
            num_rows, size["files"], 2, 0.0, DATA_DIR, seed=SEED
        )
        say(
            f"generated {num_rows} rows, {nbytes / 1e9:.2f} GB in "
            f"{time.perf_counter() - t0:.1f} s"
        )

        model = dlrm_for_data_spec(
            use_pallas_interaction=True, interpret_interaction=rehearse
        )
        optimizer = optax.adam(1e-3)
        example = {c: jnp.zeros((batch,), jnp.int32) for c in model_columns}
        state, shardings = init_state(
            model, optimizer, mesh, example, rng=jax.random.key(SEED)
        )
        n_params = sum(x.size for x in jax.tree.leaves(state.params))
        say(
            f"model: TabularDLRM, {n_params / 1e6:.1f} M parameters, "
            f"batch {batch}, mesh {dict(mesh.shape)}"
        )
        step = make_train_step(model, optimizer, mesh, shardings)
        if not rehearse:
            bsh = batch_sharding(mesh, 1)
            lowered = step.lower(
                state,
                {
                    c: jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=bsh)
                    for c in model_columns
                },
                jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=bsh),
            )
            assert (
                "tpu_custom_call" in lowered.as_text()
            ), "no Mosaic call in the train step"

        losses = []
        placed = []

        def check_placement(arr):
            """One addressable shard of batch / n rows per device."""
            shards = arr.addressable_shards
            assert len(shards) == n_dev, (len(shards), n_dev)
            assert len({s.device for s in shards}) == n_dev
            shapes = [s.data.shape for s in shards]
            assert all(s == (batch // n_dev,) for s in shapes), shapes
            placed.append(1)

        def train_on(features, label, keys):
            nonlocal state
            check_placement(label)
            check_placement(features[KEY_COLUMN])
            keys.append(np.asarray(features[KEY_COLUMN]))
            state, metrics = step(
                state, {c: features[c] for c in model_columns}, label
            )
            losses.append(metrics["loss"])

        def check_epoch(keys, what):
            got = np.sort(np.concatenate(keys))
            assert np.array_equal(
                got, np.arange(num_rows)
            ), f"{what}: keys not delivered exactly once"

        def check_losses(what, since):
            vals = [float(x) for x in jax.block_until_ready(losses[since:])]
            assert np.isfinite(vals).all(), vals
            say(
                f"  {what}: {len(vals)} steps, "
                f"loss {vals[0]:.4f} -> {vals[-1]:.4f}"
            )

        start_session()

        # -- streaming ------------------------------------------------------
        @phase("streaming")
        def _streaming():
            ds = JaxShufflingDataset(
                filenames,
                num_epochs=EPOCHS,
                num_trainers=1,
                batch_size=batch,
                rank=0,
                feature_columns=feature_columns,
                label_column=LABEL_COLUMN,
                num_reducers=size["reducers"],
                seed=SEED,
                mesh=mesh,
            )
            for epoch in range(EPOCHS):
                ds.set_epoch(epoch)
                keys = []
                for features, label in ds:
                    train_on(features, label, keys)
                check_epoch(keys, f"streaming epoch {epoch}")
            check_losses("streaming", 0)
            stats = ds.stats.as_dict()
            say(
                f"  batches staged {stats['batches_staged']}, "
                f"direct {stats['batches_staged_direct']}, "
                f"first batch {stats['first_batch_s']:.1f} s"
            )
            assert stats["batches_staged_direct"] > 0, stats

        # -- resident -------------------------------------------------------
        def resident_dataset(num_epochs, **kwargs):
            return DeviceResidentShufflingDataset(
                filenames,
                num_epochs=num_epochs,
                batch_size=batch,
                feature_columns=feature_columns,
                label_column=LABEL_COLUMN,
                seed=SEED,
                mesh=mesh,
                num_rows=num_rows,
                **kwargs,
            )

        @phase("resident")
        def _resident():
            nonlocal state
            ds = resident_dataset(2 * EPOCHS)
            say(
                f"  staged {ds.stats.bytes_staged / 1e9:.2f} GB in "
                f"{ds.stats.first_batch_s:.1f} s, "
                f"materialize_epoch={ds._materialize}"
            )
            since = len(losses)
            streams = []
            for epoch in range(EPOCHS):
                ds.set_epoch(epoch)
                keys = []
                for features, label in ds:
                    train_on(features, label, keys)
                check_epoch(keys, f"resident epoch {epoch}")
                streams.append(np.concatenate(keys))
            check_losses("resident per batch", since)
            stop_session()

            body = make_step_body(model, optimizer)

            def keyed_body(state, features, label):
                # The fused scan hands the step every delivered column; the
                # key leaves beside the loss so that exactly-once is
                # checked here too.
                state, metrics = body(
                    state, {c: features[c] for c in model_columns}, label
                )
                return state, {"loss": (metrics["loss"], features[KEY_COLUMN])}

            run_epoch = make_fused_epoch(ds, keyed_body)
            for epoch in range(EPOCHS, 2 * EPOCHS):
                state, (fused_losses, fused_keys) = run_epoch(state, epoch)
                vals = np.asarray(fused_losses)
                assert vals.shape == (num_rows // batch,), vals.shape
                assert np.isfinite(vals).all(), vals
                check_epoch(
                    [np.asarray(fused_keys).reshape(-1)],
                    f"fused epoch {epoch}",
                )
                say(
                    f"  fused epoch {epoch}: {len(vals)} steps, "
                    f"loss {vals[0]:.4f} -> {vals[-1]:.4f}"
                )
            ds.close()

            # The other schedule (a gather per batch in place of one
            # permuted copy per epoch, taken when the copy would not fit)
            # must deliver the same stream.
            ds = resident_dataset(1, materialize_epoch=False)
            ds.set_epoch(0)
            keys = [np.asarray(features[KEY_COLUMN]) for features, _ in ds]
            assert np.array_equal(
                np.concatenate(keys), streams[0]
            ), "the gather schedule delivered another stream"
            say("  gather schedule: epoch 0 delivered the same key stream")
            ds.close()

            # The labels are uniform noise, so the loss starts near ln 2 and
            # falls only as the tables memorize rows they meet again:
            # slowly, and within one epoch by less than a batch's own
            # scatter. Over the run's four passes the fall is plain.
            per_epoch = num_rows // batch
            first = float(np.mean([float(x) for x in losses[:per_epoch]]))
            last = float(np.mean(vals))
            say(
                f"  loss, mean of first epoch {first:.4f} -> "
                f"mean of last epoch {last:.4f}"
            )
            assert last < first - 0.002, (first, last)

        # -- placement ------------------------------------------------------
        @phase("placement")
        def _placement():
            jax.block_until_ready(state.params)
            say(
                f"  {len(placed)} delivered arrays had one shard of "
                f"{batch // n_dev} rows on each of {n_dev} devices"
            )
            for dev, before, now in zip(
                devices, in_use_start, bytes_in_use()
            ):
                stats = dev.memory_stats()
                if stats is None:
                    assert rehearse, f"{dev} reports no memory_stats"
                    say(f"  {dev}: no memory_stats on this backend")
                    continue
                say(
                    f"  {dev}: bytes_in_use {before / 1e9:.2f} -> "
                    f"{now / 1e9:.2f} GB, "
                    f"peak {stats['peak_bytes_in_use'] / 1e9:.2f} of "
                    f"limit {stats['bytes_limit'] / 1e9:.2f} GB"
                )
                assert now > before, f"{dev} holds no more than at the start"

        store = runtime.store_stats()
        assert store.num_objects == 0, f"store not empty: {store}"
    finally:
        stop_session()
        runtime.shutdown()
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    if trace_dir:
        xplane = sorted(
            glob.glob(
                os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
            )
        )[-1]
        os.makedirs(
            os.path.dirname(os.path.abspath(args.trace_out)), exist_ok=True
        )
        telemetry.trace_export(args.trace_out, xplane=xplane)
        check_merged_trace(args.trace_out, say)
        shutil.rmtree(trace_dir, ignore_errors=True)
    leaked = [
        f
        for f in os.listdir(ctx.store.shm_dir)
        if f.startswith(ctx.store.session)
    ]
    assert not leaked, f"segments left in {ctx.store.shm_dir}: {leaked[:5]}"
    assert not os.path.exists(ctx.runtime_dir), ctx.runtime_dir

    # -- cache ------------------------------------------------------------
    written = cache_entries() - entries_before
    say(
        f"compile cache: {cache_dir}, {written} entries written by this "
        f"run ({entries_before} found at start)"
    )
    say(
        f"phases (s): {json.dumps(phases)}; "
        f"wall {time.perf_counter() - t_start:.1f} s"
    )
    if rehearse:
        say("every phase ran; a rehearsal prints no result")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
