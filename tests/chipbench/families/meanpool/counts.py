"""The mean-pool model's counts, from the configuration's sizes alone."""

from typing import Dict, List


def model_columns(cfg: dict) -> List[str]:
    return sorted(c for c in cfg["data_spec"] if c != cfg["label_column"])


def vocab_sizes(cfg: dict) -> Dict[str, int]:
    return {c: int(cfg["data_spec"][c][1]) for c in model_columns(cfg)}


def num_parameters(cfg: dict) -> int:
    w, h = int(cfg["model"]["width"]), int(cfg["model"]["hidden"])
    return sum(vocab_sizes(cfg).values()) * w + (w * h + h) + (h + 1)


def state_bytes(cfg: dict) -> int:
    return 3 * 4 * num_parameters(cfg)


def flops_per_row(cfg: dict) -> int:
    """Three matmul-sized passes over the two dense layers."""
    w, h = int(cfg["model"]["width"]), int(cfg["model"]["hidden"])
    return 3 * 2 * (w * h + h)
