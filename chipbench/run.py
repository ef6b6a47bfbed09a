"""The benchmark's entry point.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``compared``
(each number of the comparison beside its limit). Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
``--rehearse-on-cpu`` walks the same run at the configuration's toy sizes
with the kernels in the Pallas interpreter, to debug the harness; it prints
no result line either.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    # Workers spawned by the runtime inherit this path and find chipbench.
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument(
        "--dump-trace-summary", default=None,
        help="with --trace 1: write the trace's planes, lines and top names here",
    )
    args = ap.parse_args(argv)

    from chipbench import harness

    bench = harness.load_benchmark()
    cell, _, _ = harness.load_cell(bench, args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]

    import jax

    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    wanted = "cpu" if args.rehearse_on_cpu else "tpu"
    tag = "[chipbench CPU REHEARSAL - not a chip run]" if args.rehearse_on_cpu else "[chipbench]"

    def say(msg: str) -> None:
        at = time.perf_counter() - T_START
        print(f"{tag} {at:6.1f}s {msg}", file=sys.stderr, flush=True)

    if devices[0].platform != wanted or len(devices) < int(cell["chips"]):
        say(
            f"FAILED: found {len(devices)} x {devices[0].platform!r}; the cell "
            f"asks for {cell['chips']} x {wanted!r}"
        )
        return 1
    say(f"compile cache: {cache_dir}")
    result = harness.run_cell(
        bench, args.workload, args.seed, seconds, bool(args.trace),
        rehearse=args.rehearse_on_cpu, t_start=T_START, say=say,
        dump_trace=args.dump_trace_summary,
    )
    for name, c in result["compared"].items():
        say(
            f"compared {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}"
        )
    if args.rehearse_on_cpu:
        say(f"correct={result['correct']}; a rehearsal prints no result")
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
