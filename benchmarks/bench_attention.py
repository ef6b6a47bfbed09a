"""Micro-benchmark: the attention stack across schedules and lowerings.

Times, at several sequence lengths, on whatever backend is up:

* dense XLA reference (``attention_reference``)
* blockwise XLA (``blockwise_attention`` — no [T, T] materialization)
* Pallas flash kernel (``flash_attention``; interpret mode off-TPU is
  meaningless for timing, so it only runs compiled on TPU)
* ring schedule over all local devices (``make_ring_attention``)
* Ulysses schedule over all local devices (``make_ulysses_attention``)

Prints one JSON line per (schedule, seq_len) so results can be diffed
across rounds. Run:

    python benchmarks/bench_attention.py [--seqs 1024,4096] [--reps 20]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _time(fn, args_, reps: int) -> float:
    import jax

    out = fn(*args_)
    jax.block_until_ready(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args_)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", type=str, default="1024,4096")
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--reps", type=int, default=20)
    parser.add_argument("--causal", action="store_true")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from ray_shuffling_data_loader_tpu.ops import (
        attention_reference,
        blockwise_attention,
        flash_attention,
        make_ring_attention,
        make_ulysses_attention,
    )
    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    rng = np.random.default_rng(0)

    for seq in [int(s) for s in args.seqs.split(",")]:
        shape = (args.batch, seq, args.heads, args.head_dim)
        q, k, v = (
            jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(3)
        )
        schedules = {
            "dense": jax.jit(
                lambda q, k, v: attention_reference(
                    q, k, v, causal=args.causal
                )
            ),
            "blockwise": jax.jit(
                lambda q, k, v: blockwise_attention(
                    q, k, v, causal=args.causal
                )
            ),
        }
        if platform == "tpu":
            schedules["flash"] = jax.jit(
                lambda q, k, v: flash_attention(
                    q,
                    k,
                    v,
                    causal=args.causal,
                    use_pallas=True,
                    interpret=False,
                )
            )
        # Pre-shard inputs for the sequence-parallel schedules: without
        # this, every timed rep would include a full scatter of q/k/v
        # from device 0, which the single-device schedules never pay.
        sharded_inputs = None
        if seq % n_dev == 0 and n_dev > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(mesh, P(None, "sp", None, None))
            sharded_inputs = tuple(jax.device_put(x, sh) for x in (q, k, v))
            schedules["ring"] = make_ring_attention(
                mesh, "sp", causal=args.causal
            )
            if args.heads % n_dev == 0:
                schedules["ulysses"] = make_ulysses_attention(
                    mesh, "sp", causal=args.causal
                )
        for name, fn in schedules.items():
            inputs = (
                sharded_inputs
                if name in ("ring", "ulysses")
                else (q, k, v)
            )
            try:
                dt = _time(fn, inputs, args.reps)
            except Exception as exc:  # e.g. OOM at long T for dense
                print(
                    json.dumps(
                        {
                            "schedule": name,
                            "seq": seq,
                            "error": f"{type(exc).__name__}: {exc}"[:200],
                        }
                    ),
                    flush=True,
                )
                continue
            print(
                json.dumps(
                    {
                        "schedule": name,
                        "seq": seq,
                        "batch": args.batch,
                        "heads": args.heads,
                        "head_dim": args.head_dim,
                        "causal": args.causal,
                        "ms": round(dt * 1e3, 3),
                        "backend": platform,
                        "devices": n_dev,
                    }
                ),
                flush=True,
            )


if __name__ == "__main__":
    sys.exit(main())
