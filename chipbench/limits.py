"""Readings that the comparison's limits are set from, many seeds to a process.

    python3 chipbench/limits.py --config <name> --seeds 1,2,3 [--controls 4]

For each seed, at the configuration's own size: the program's first three
steps (its compiled step, started from the benchmark's weights, on three
batches of rows that all differ, straight from the generator) against the
family's plain reference: the lower readings. For the first ``--controls``
seeds also the control (the reference in the family's next lower precision,
put in the program's place) and the fault "half of the batch left out, the
mean taken over the rest" (planted in the reference put in the program's
place): the upper readings. "A step that returns its state unchanged" reads
1 on ``change_norm_gap`` by construction and needs no run. Not part of a
benchmark run; the numbers go into ``PERF.md`` and the configuration's
``limits``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def generator_batches(cfg: dict, seed: int, steps: int):
    """``steps`` batches of rows that all differ, straight from the
    generator, as the files would hold them: ``[{column: numpy}]``."""
    from chipbench import datagen

    batch = int(cfg["batch_size"])
    raw = datagen.generate_row_group(cfg["data_spec"], 0, 0, steps * batch, seed)
    return [
        {
            c: datagen.narrowed(col[i * batch : (i + 1) * batch])
            for c, col in raw.items()
        }
        for i in range(steps)
    ]


def program_readings(cfg, family, mesh, seed, batches, rehearse=False):
    """The program's compiled step driven over ``batches`` from the
    benchmark's weights; the readings the comparison takes."""
    import jax

    from ray_shuffling_data_loader_tpu.parallel import batch_sharding

    from chipbench import harness

    bsh = batch_sharding(mesh, 1)
    program = harness.Program(cfg, family, mesh, seed, rehearse)
    side = program.side
    for rows in batches:
        loss = program.step_on(
            {c: jax.device_put(rows[c], bsh) for c in side.feature_columns},
            jax.device_put(rows[side.label_column], bsh)
            if side.label_column
            else None,
        )
        program.record(loss)
    program.record_change()
    readings = program.fetch_readings()
    program.free()
    return readings


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=4)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from ray_shuffling_data_loader_tpu.parallel import make_mesh
    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    from chipbench import check, harness

    enable_compile_cache()
    wanted = "cpu" if args.rehearse_on_cpu else "tpu"
    if jax.devices()[0].platform != wanted:
        print(f"FAILED: platform {jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    bench = harness.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    if args.rehearse_on_cpu:
        cfg = {**cfg, **cfg["rehearsal"]}
    family = harness.load_family(cfg, paths0=bench["paths"][0])
    reference = family.reference
    mesh = make_mesh(devices=jax.devices()[:1])
    batch = int(cfg["batch_size"])
    ref_plain = reference.Reference(cfg)
    ref_control = reference.Reference(cfg, quant=reference.CONTROL)
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        batches = generator_batches(cfg, seed, args.steps)
        prog = program_readings(
            cfg, family, mesh, seed, batches, args.rehearse_on_cpu
        )
        make = lambda: reference.init_params(cfg, seed)  # noqa: E731
        ref_batches = [reference.batch_of(cfg, b) for b in batches]
        ref = ref_plain.follow(make, ref_batches)
        sides = {"program": prog}
        if n < args.controls:
            sides["control_" + reference.CONTROL] = ref_control.follow(
                make, ref_batches
            )
            sides["fault_half_batch"] = ref_plain.follow(
                make, ref_batches, rows_used=batch // 2
            )
        row = {"seed": seed}
        for side, readings in sides.items():
            row[side] = check.training_numbers(readings, ref)
        # Every leaf's readings too, so that another number can be tried
        # on them without another run.
        row["readings"] = {"reference": ref, **sides}
        rows.append(row)
        print(json.dumps({k: v for k, v in row.items() if k != "readings"}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
