"""Plain float32 reference of the mean-pool model, its weights from the
seed, and its control: every matmul operand rounded to bfloat16, the nearest
precision below the float32 the configuration states."""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.follow import AdamFollower, seed_key

from . import counts

CONTROL = "bf16"


def init_params(cfg: dict, seed: int, sharding=None):
    w, h = int(cfg["model"]["width"]), int(cfg["model"]["hidden"])
    shapes = {f"table_{c}": (v, w) for c, v in counts.vocab_sizes(cfg).items()}
    shapes.update({"hidden.w": (w, h), "hidden.b": (h,),
                   "logit.w": (h, 1), "logit.b": (1,)})

    def make(key):
        return {
            name: jnp.zeros(shape, jnp.float32) if name.endswith(".b")
            else jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            / np.sqrt(shape[0] if name.endswith(".w") else w)
            for i, (name, shape) in enumerate(shapes.items())
        }

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def _rounded(x):
    return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def loss_sum(cfg, params, features, labels, quant: Optional[str] = None):
    q = _rounded if quant == "bf16" else (lambda x: x)
    cols = counts.model_columns(cfg)
    x = sum(
        q(params[f"table_{c}"][features[c] % params[f"table_{c}"].shape[0]])
        for c in cols
    ) / len(cols)
    x = jax.nn.relu(q(x) @ q(params["hidden.w"]) + params["hidden.b"])
    logits = (q(x) @ q(params["logit.w"]) + params["logit.b"]).reshape(-1)
    return -jnp.sum(
        labels * jax.nn.log_sigmoid(logits)
        + (1.0 - labels) * jax.nn.log_sigmoid(-logits)
    )


def batch_of(cfg: dict, rows) -> tuple:
    return (
        {c: rows[c] for c in counts.model_columns(cfg)},
        rows[cfg["label_column"]].astype(np.float32),
    )


class Reference(AdamFollower):
    def __init__(self, cfg: dict, quant: Optional[str] = None):
        super().__init__(
            cfg["optimizer"],
            lambda params, block: loss_sum(cfg, params, *block, quant),
            block_rows=1024,
        )
