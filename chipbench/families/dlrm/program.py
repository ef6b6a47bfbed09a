"""The program's side of the family: the one place that imports its model.

Builds the DLRM, the optimizer, the state and the compiled step through the
program's normal entry points (``dlrm_for_data_spec``, ``init_state``,
``make_train_step``), names the columns the loader is asked for, turns a
delivered batch into the step's inputs, and carries the benchmark's flat
weights (``reference.init_params``) into the program's tree and back.
"""

from __future__ import annotations

from . import counts


class Side:
    """``state`` and ``step`` as the program made them; ``step(state,
    *inputs(features, label))`` returns ``(state, {"loss": ..})``."""

    def __init__(self, cfg: dict, mesh, seed: int, rehearse: bool = False):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_shuffling_data_loader_tpu.models import dlrm_for_data_spec
        from ray_shuffling_data_loader_tpu.parallel import (
            init_state,
            make_train_step,
        )

        self.cfg = cfg
        self.feature_columns = counts.model_columns(cfg)
        self.label_column = cfg["label_column"]
        model = dlrm_for_data_spec(
            embed_dim=int(cfg["model"]["embed_dim"]),
            top_mlp=tuple(cfg["model"]["top_mlp"]),
            vocab_cap=int(cfg.get("vocab_cap", 0)) or None,
            use_pallas_interaction=True,
            interpret_interaction=rehearse,
        )
        opt = cfg["optimizer"]
        optimizer = optax.adam(
            float(opt["learning_rate"]), b1=float(opt["b1"]),
            b2=float(opt["b2"]), eps=float(opt["eps"]),
        )
        batch = int(cfg["batch_size"])
        example = {
            c: jnp.zeros((batch,), jnp.int32) for c in self.feature_columns
        }
        self.state, shardings = init_state(
            model, optimizer, mesh, example,
            rng=jax.random.key(seed & 0x7FFFFFFF),
        )
        self.step = make_train_step(model, optimizer, mesh, shardings)

    def inputs(self, features, label) -> tuple:
        """A delivered batch as the step takes it."""
        return {c: features[c] for c in self.feature_columns}, label

    def tree(self, weights: dict):
        """The benchmark's flat weights in the program's (flax) tree."""
        inner = {
            f"embed_{c}": weights[f"embed_{c}"] for c in self.feature_columns
        }
        for i in range(len(counts.mlp_shapes(self.cfg))):
            inner[f"Dense_{i}"] = {
                "kernel": weights[f"dense_{i}.w"],
                "bias": weights[f"dense_{i}.b"],
            }
        return {"params": inner}

    def flat(self, tree) -> dict:
        """The program's tree back under the reference's leaf names."""
        inner = tree["params"]
        out = {f"embed_{c}": inner[f"embed_{c}"] for c in self.feature_columns}
        for i in range(len(counts.mlp_shapes(self.cfg))):
            out[f"dense_{i}.w"] = inner[f"Dense_{i}"]["kernel"]
            out[f"dense_{i}.b"] = inner[f"Dense_{i}"]["bias"]
        return out
