"""The program's side: a flax module of the test's own, trained through
the program's normal entry points (``init_state``, ``make_train_step``)."""

from typing import Sequence, Tuple

import flax.linen as nn

from . import counts


class MeanPool(nn.Module):
    vocab: Sequence[Tuple[str, int]]
    width: int
    hidden: int

    @nn.compact
    def __call__(self, features):
        pooled = sum(
            nn.Embed(size, self.width, name=f"table_{col}")(features[col] % size)
            for col, size in self.vocab
        ) / len(self.vocab)
        x = nn.relu(nn.Dense(self.hidden, name="hidden")(pooled))
        return nn.Dense(1, name="logit")(x).reshape(-1)


class Side:
    def __init__(self, cfg: dict, mesh, seed: int, rehearse: bool = False):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_shuffling_data_loader_tpu.parallel import (
            init_state,
            make_train_step,
        )

        self.feature_columns = counts.model_columns(cfg)
        self.label_column = cfg["label_column"]
        model = MeanPool(
            tuple(counts.vocab_sizes(cfg).items()),
            int(cfg["model"]["width"]), int(cfg["model"]["hidden"]),
        )
        opt = cfg["optimizer"]
        optimizer = optax.adam(
            float(opt["learning_rate"]), b1=float(opt["b1"]),
            b2=float(opt["b2"]), eps=float(opt["eps"]),
        )
        example = {
            c: jnp.zeros((int(cfg["batch_size"]),), jnp.int32)
            for c in self.feature_columns
        }
        self.state, shardings = init_state(
            model, optimizer, mesh, example, rng=jax.random.key(seed & 0x7FFFFFFF)
        )
        self.step = make_train_step(model, optimizer, mesh, shardings)

    def inputs(self, features, label) -> tuple:
        return {c: features[c] for c in self.feature_columns}, label

    def tree(self, weights: dict):
        inner = {
            f"table_{c}": {"embedding": weights[f"table_{c}"]}
            for c in self.feature_columns
        }
        for name in ("hidden", "logit"):
            inner[name] = {"kernel": weights[name + ".w"], "bias": weights[name + ".b"]}
        return {"params": inner}

    def flat(self, tree) -> dict:
        inner = tree["params"]
        out = {f"table_{c}": inner[f"table_{c}"]["embedding"] for c in self.feature_columns}
        for name in ("hidden", "logit"):
            out[name + ".w"] = inner[name]["kernel"]
            out[name + ".b"] = inner[name]["bias"]
        return out
