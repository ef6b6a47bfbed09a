"""The program's side of the family: the one place that imports its model.

Builds Kimi-VL's language model of this chip's share, the optimizer, the
state and the compiled step through the program's normal entry points
(``KimiLM``, ``init_state``, ``make_train_step``), names the column the
loader is asked for, turns a delivered batch into the step's one input, and
carries the benchmark's flat weights (``reference.init_params``) into the
program's tree and back.
"""

from __future__ import annotations

from . import counts

# The reference's leaf name after ``l<i>.`` -> the path in the program's
# layer (a flax tree).
LAYER_LEAVES = {
    "in_norm": ("input_layernorm", "scale"),
    "post_norm": ("post_attention_layernorm", "scale"),
    "attn.q": ("self_attn", "q_proj"),
    "attn.kv_a": ("self_attn", "kv_a_proj"),
    "attn.kv_norm": ("self_attn", "kv_a_norm", "scale"),
    "attn.kv_b": ("self_attn", "kv_b_proj"),
    "attn.o": ("self_attn", "out_proj"),
    "ffn.w1": ("mlp", "w1"),
    "ffn.w3": ("mlp", "w3"),
    "ffn.w2": ("mlp", "w2"),
    "shared.w1": ("shared_experts", "w1"),
    "shared.w3": ("shared_experts", "w3"),
    "shared.w2": ("shared_experts", "w2"),
    "moe.gate": ("mlp", "gate"),
    "moe.bias": ("mlp", "expert_bias"),
    "moe.w1": ("mlp", "w1"),
    "moe.w3": ("mlp", "w3"),
    "moe.w2": ("mlp", "w2"),
}


def _path(leaf: str) -> tuple:
    """The program's path of a reference leaf."""
    if "." not in leaf:
        return (leaf, "scale") if leaf == "final_norm" else (leaf,)
    layer, rest = leaf.split(".", 1)
    return ("layer_" + layer[1:], *LAYER_LEAVES[rest])


def model_config(cfg: dict) -> dict:
    """The configuration's keys as the program's model takes them: the
    file's ``n_routed_experts`` is what is held here, the published count
    is what the router scores."""
    return {
        **cfg,
        "n_routed_experts": counts.experts_routed(cfg),
        "experts_held": counts.experts_held(cfg),
    }


class Side:
    """``state`` and ``step`` as the program made them; ``step(state,
    *inputs(features, label))`` returns ``(state, {"loss": .., ..})``."""

    def __init__(self, cfg: dict, mesh, seed: int, rehearse: bool = False):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_shuffling_data_loader_tpu.models.kimi import KimiConfig, KimiLM
        from ray_shuffling_data_loader_tpu.parallel import (
            init_state,
            make_train_step,
        )

        self.cfg = cfg
        self.feature_columns = counts.model_columns(cfg)
        self.label_column = None
        self.leaves = list(counts.leaf_shapes(cfg))
        kernels = cfg["kernels"]
        model = KimiLM(
            KimiConfig.from_dict(model_config(cfg)),
            compute_dtype=jnp.dtype(cfg["model"]["compute_dtype"]),
            use_pallas=True,
            interpret=rehearse,
            block_q=int(kernels["attention_block_q"]),
            block_k=int(kernels["attention_block_k"]),
            row_tile=int(kernels["expert_row_tile"]),
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
