"""Device-side ops: Pallas TPU kernels with XLA reference fallbacks."""

from ray_shuffling_data_loader_tpu.ops.interaction import (  # noqa: F401
    dot_interaction,
    dot_interaction_reference,
    num_pairs,
)
from ray_shuffling_data_loader_tpu.ops.embedding import (  # noqa: F401
    embedding_lookup,
    lookup_pack,
    packed_tables,
)
from ray_shuffling_data_loader_tpu.ops.flash_attention import (  # noqa: F401
    flash_attention,
)
from ray_shuffling_data_loader_tpu.ops.selective_scan import (  # noqa: F401
    selective_scan,
    selective_scan_reference,
)
from ray_shuffling_data_loader_tpu.ops.ring_attention import (  # noqa: F401
    attention_reference,
    blockwise_attention,
    make_ring_attention,
    make_ulysses_attention,
    ring_attention,
)

__all__ = [
    "dot_interaction",
    "dot_interaction_reference",
    "num_pairs",
    "embedding_lookup",
    "lookup_pack",
    "packed_tables",
    "attention_reference",
    "blockwise_attention",
    "flash_attention",
    "make_ring_attention",
    "make_ulysses_attention",
    "ring_attention",
    "selective_scan",
    "selective_scan_reference",
]
