"""The program's side of the family: the one place that imports its model.

Builds the Phi-4-mini-flash of this chip's share, the optimizer, the state
and the compiled step through the program's normal entry points
(``Phi4FlashLM``, ``init_state``, ``make_train_step``), names the column the
loader is asked for, turns a delivered batch into the step's one input, and
carries the benchmark's flat weights (``reference.init_params``) into the
program's tree and back.
"""

from __future__ import annotations

from . import counts

# The reference's leaf name after ``l<i>.`` -> the path in the program's
# layer (a flax tree). Every kind of mixer is the layer's ``mixer``.
_ATTENTION = {
    "o": "out_proj", "o_bias": "out_proj_bias", "lq1": "lambda_q1",
    "lk1": "lambda_k1", "lq2": "lambda_q2", "lk2": "lambda_k2",
}
LAYER_LEAVES = {
    **{f"norm{i}.{p}": (f"norm{i}", p) for i in (1, 2) for p in ("scale", "bias")},
    **{f"mlp.{w}": ("mlp", w) for w in ("w1", "w3", "w2")},
    "ssm.in": ("mixer", "in_proj"), "ssm.conv": ("mixer", "conv"),
    "ssm.conv_bias": ("mixer", "conv_bias"), "ssm.x": ("mixer", "x_proj"),
    "ssm.dt": ("mixer", "dt_proj"), "ssm.dt_bias": ("mixer", "dt_bias"),
    "ssm.A_log": ("mixer", "A_log"), "ssm.D": ("mixer", "D"),
    "ssm.out": ("mixer", "out_proj"),
    "gmu.in": ("mixer", "in_proj"), "gmu.out": ("mixer", "out_proj"),
    "attn.qkv": ("mixer", "qkv_proj"), "cross.q": ("mixer", "q_proj"),
    **{f"{a}.{n}_bias": ("mixer", f"{n}_bias") for a in ("attn", "cross") for n in "qkv"},
    **{f"{a}.{k}": ("mixer", v) for a in ("attn", "cross") for k, v in _ATTENTION.items()},
    **{f"{a}.norm": ("mixer", "head_norm", "scale") for a in ("attn", "cross")},
}


def _path(leaf: str) -> tuple:
    """The program's path of a reference leaf."""
    if not leaf.startswith("l"):
        return tuple(leaf.split("."))  # embed, final_norm.scale / .bias
    layer, rest = leaf.split(".", 1)
    return ("layer_" + layer[1:], *LAYER_LEAVES[rest])


def model_config(cfg: dict) -> dict:
    """The configuration's keys as the program's model takes them: the
    file's ``num_hidden_layers`` is what is kept here, the published count
    is what the kinds of layers follow from; the scan's sizes lie in
    ``ssm``."""
    ssm = cfg["ssm"]
    return {
        **cfg,
        "published_layers": counts.published_layers(cfg),
        "ssm_state_size": int(ssm["state_size"]),
        "ssm_conv_kernel": int(ssm["conv_kernel"]),
        "ssm_expand": int(ssm["expand"]),
        "ssm_dt_rank": int(ssm["dt_rank"]),
    }


class Side:
    """``state`` and ``step`` as the program made them; ``step(state,
    *inputs(features, label))`` returns ``(state, {"loss": ..})``."""

    def __init__(self, cfg: dict, mesh, seed: int, rehearse: bool = False):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_shuffling_data_loader_tpu.models.phi4flash import (
            Phi4FlashConfig,
            Phi4FlashLM,
        )
        from ray_shuffling_data_loader_tpu.parallel import (
            init_state,
            make_train_step,
        )

        self.cfg = cfg
        self.feature_columns = counts.model_columns(cfg)
        self.label_column = None
        self.leaves = list(counts.leaf_shapes(cfg))
        kernels = cfg["kernels"]
        model = Phi4FlashLM(
            Phi4FlashConfig.from_dict(model_config(cfg)),
            compute_dtype=jnp.dtype(cfg["model"]["compute_dtype"]),
            use_pallas=True,
            interpret=rehearse,
            block_q=int(kernels["attention_block_q"]),
            block_k=int(kernels["attention_block_k"]),
        )
        opt = cfg["optimizer"]
        optimizer = optax.adam(
            float(opt["learning_rate"]), b1=float(opt["b1"]),
            b2=float(opt["b2"]), eps=float(opt["eps"]),
        )
        example = {
            c: jnp.zeros((int(cfg["batch_size"]), counts.seq_len(cfg)), jnp.int32)
            for c in self.feature_columns
        }
        self.state, shardings = init_state(
            model, optimizer, mesh, example,
            rng=jax.random.key(seed & 0x7FFFFFFF),
        )
        self.step = make_train_step(model, optimizer, mesh, shardings)

    def inputs(self, features, label) -> tuple:
        """A delivered batch as the step takes it: the features only."""
        return ({c: features[c] for c in self.feature_columns},)

    def tree(self, weights: dict):
        """The benchmark's flat weights in the program's (flax) tree."""
        inner: dict = {}
        for leaf in self.leaves:
            *parents, last = _path(leaf)
            node = inner
            for name in parents:
                node = node.setdefault(name, {})
            node[last] = weights[leaf]
        return {"params": inner}

    def flat(self, tree) -> dict:
        """The program's tree back under the reference's leaf names."""
        out = {}
        for leaf in self.leaves:
            node = tree["params"]
            for name in _path(leaf):
                node = node[name]
            out[leaf] = node
        return out
