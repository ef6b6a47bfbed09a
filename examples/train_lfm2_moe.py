"""Train the LFM2-MoE (gated short convolutions, grouped-query attention, a
sparse mixture of experts) on token sequences streamed from Parquet.

The corpus is one ``fixed_size_list<int32>[seq_len]`` column: a row is a
sequence. ``JaxShufflingDataset`` shuffles the rows every epoch and delivers
them to the device as ``{"tokens": [batch, seq_len]}`` with no label
(``label_column=None``), straight off the reducers' packed segments; the
model brings its own next-token loss, and ``init_state`` /
``make_train_step`` are the entry points every model of the repo uses. The
sizes are the toy sizes the benchmark rehearses on the CPU
(``chipbench/configs/lfm2-24b-a2b-ep8.json``: its published widths run on
the chip only); ``--experts-held`` of the ``--experts`` routed over are held
here, as on one chip of an expert-parallel deployment.

    JAX_PLATFORMS=cpu python examples/train_lfm2_moe.py

exits 0 once the loss has fallen.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--sequences", type=int, default=256)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--experts", type=int, default=16)
    p.add_argument("--experts-held", type=int, default=4)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def write_corpus(data_dir, sequences, seq_len, vocab, files, seed):
    """Sequences that can be learned: each counts up from a random start by
    a random stride, modulo the vocabulary. One wide column and a key."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    start = rng.integers(0, vocab, (sequences, 1))
    stride = rng.integers(1, 4, (sequences, 1))
    tokens = ((start + stride * np.arange(seq_len)) % vocab).astype(np.int32)
    names = []
    for i, rows in enumerate(np.array_split(np.arange(sequences), files)):
        table = pa.table({
            "key": pa.array(rows.astype(np.int64)),
            "tokens": pa.FixedSizeListArray.from_arrays(
                pa.array(tokens[rows].reshape(-1)), seq_len
            ),
        })
        names.append(os.path.join(data_dir, f"tokens_{i}.parquet"))
        pq.write_table(table, names[-1], row_group_size=max(1, len(rows) // 2))
    return names


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax
    import optax

    from ray_shuffling_data_loader_tpu import runtime
    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models.lfm2_moe import (
        Lfm2MoeConfig,
        Lfm2MoeLM,
    )
    from ray_shuffling_data_loader_tpu.parallel import (
        init_state,
        make_mesh,
        make_train_step,
    )

    cfg = Lfm2MoeConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        intermediate_size=3 * args.hidden // 2,
        moe_intermediate_size=args.hidden // 2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=5,
        layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv"),
        num_dense_layers=2,
        first_layer=1,
        num_experts=args.experts,
        num_experts_per_tok=4,
        experts_held=args.experts_held,
    )
    # The kernels where the backend has them (a TPU); elsewhere the XLA paths.
    model = Lfm2MoeLM(cfg, block_q=32, block_k=32, row_tile=8)
    mesh = make_mesh(devices=jax.devices()[:1])
    optimizer = optax.adam(args.lr)

    runtime.init()
    data_dir = tempfile.mkdtemp(prefix="lfm2-tokens-")
    try:
        files = write_corpus(
            data_dir, args.sequences, args.seq_len, args.vocab, 4, args.seed
        )
        ds = JaxShufflingDataset(
            files, num_epochs=args.epochs, num_trainers=1,
            batch_size=args.batch, rank=0, feature_columns=["tokens"],
            label_column=None, num_reducers=2, seed=args.seed, mesh=mesh,
        )
        state = step = None
        first = last = None
        for epoch in range(args.epochs):
            ds.set_epoch(epoch)
            for features, _ in ds:
                if state is None:
                    state, shardings = init_state(
                        model, optimizer, mesh, features,
                        rng=jax.random.key(args.seed),
                    )
                    step = make_train_step(model, optimizer, mesh, shardings)
                state, metrics = step(state, features)
                last = float(metrics["loss"])
                first = last if first is None else first
            load = metrics["moe_load"]
            print(
                f"epoch {epoch}: loss {last:.3f}; tokens routed to the "
                f"{args.experts_held} experts held, last step: "
                f"max {int(load.max())}, mean {float(load.mean()):.1f}",
                flush=True,
            )
        stats = ds.stats.as_dict()
        print(
            f"{stats['batches_staged_direct']} of {stats['batches_staged']} "
            "batches went to the device straight off the packed segments"
        )
    finally:
        runtime.shutdown()
    if not last < 0.9 * first:
        print(f"loss did not fall: {first:.3f} -> {last:.3f}")
        return 1
    print(f"loss fell: {first:.3f} -> {last:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
