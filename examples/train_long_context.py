"""Long-context training recipe: causal LM with sequence parallelism.

End-to-end demonstration of the long-context path (no reference analog —
the reference repo is a tabular loader with a mocked train step): a
causal transformer trains over sequences sharded across a mesh axis, so
activation memory per device scales with ``seq / sp`` instead of
``seq``. The mesh is 2-D ``(data, sp)``: batch over ``data``, sequence
over ``sp``; gradients reduce over both axes automatically under the
sharding-annotated ``jit``.

Runs anywhere — CPU smoke with 8 virtual devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_long_context.py --dp 2 --sp 4

On a TPU slice, drop the env vars and size ``--dp/--sp`` to the chips;
``--attention ulysses`` switches the sequence schedule (heads must be a
multiple of ``sp``).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dp", type=int, default=2, help="data-axis size")
    p.add_argument("--sp", type=int, default=4, help="sequence-axis size")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--embed-dim", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument(
        "--attention", choices=("ring", "ulysses", "dense"), default="ring"
    )
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.steps < 2:
        p.error("--steps must be >= 2 (the run asserts the loss falls)")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax

    from ray_shuffling_data_loader_tpu.utils import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_shuffling_data_loader_tpu.models import (
        CausalLM,
        next_token_loss,
        synthetic_tokens,
    )
    import functools

    from ray_shuffling_data_loader_tpu.ops import (
        attention_reference,
        make_ring_attention,
        make_ulysses_attention,
    )

    devices = jax.devices()
    need = args.dp * args.sp
    if len(devices) < need:
        raise SystemExit(
            f"need {need} devices for dp={args.dp} x sp={args.sp}, "
            f"have {len(devices)}"
        )
    mesh = Mesh(
        np.array(devices[:need]).reshape(args.dp, args.sp), ("data", "sp")
    )
    print(f"mesh: {dict(mesh.shape)}, seq {args.seq_len} -> "
          f"{args.seq_len // args.sp} per device", flush=True)

    if args.attention == "ring":
        attention_fn = make_ring_attention(
            mesh, "sp", causal=True, batch_axis="data"
        )
    elif args.attention == "ulysses":
        attention_fn = make_ulysses_attention(
            mesh, "sp", causal=True, batch_axis="data"
        )
    else:
        # Explicitly the XLA dense reference — the numerics baseline for
        # the two sequence schedules. (attention_fn=None would mean the
        # model's default, i.e. the flash auto-policy, not dense.)
        attention_fn = functools.partial(attention_reference, causal=True)

    model = CausalLM(
        vocab_size=args.vocab,
        max_seq_len=args.seq_len,
        embed_dim=args.embed_dim,
        num_layers=args.layers,
        num_heads=args.heads,
        attention_fn=attention_fn,
    )
    tokens_host = synthetic_tokens(
        args.batch, args.seq_len, args.vocab, seed=args.seed
    )
    token_sharding = NamedSharding(mesh, P("data", "sp"))
    tokens = jax.device_put(jnp.asarray(tokens_host), token_sharding)

    params = model.init(jax.random.key(args.seed), tokens)
    optimizer = optax.adam(args.lr)
    opt_state = optimizer.init(params)
    replicated = NamedSharding(mesh, P())

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(params):
            return next_token_loss(model.apply(params, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params = jax.device_put(params, replicated)
    opt_state = jax.device_put(opt_state, replicated)

    first = last = None
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        last = float(loss)
        if first is None:
            first = last
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {last:.4f}", flush=True)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    print(
        f"{args.steps} steps in {dt:.1f}s ({args.attention} attention); "
        f"loss {first:.4f} -> {last:.4f}",
        flush=True,
    )
    if not last < first:
        print("warning: loss did not decrease", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
