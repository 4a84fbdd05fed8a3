"""Bytes of a block-diffusion step's attention over grouped-query keys
and values, computed from the published keys: the numerator of
``block_attn_roofline_share``. They count the work the algorithm needs
whatever implements it, and are kept with the benchmark (beside
``flops.py`` and ``expert_latent_counts.py``) so that no later PR can
change what a share is a share of."""
from __future__ import annotations

from typing import Any, Dict


def kv_token_layer_bytes(m: Dict[str, Any], itemsize: int = 2) -> int:
    """A token's keys and values in one layer: ``num_key_value_heads``
    heads of ``head_dim``, K and V (4 x 128 x 2 values: 2,048 bytes in
    bf16)."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def block_step_bytes(m: Dict[str, Any], live_tokens: float,
                     itemsize: int = 2) -> float:
    """The least a pass's attention reads: every live token's keys and
    values once a layer (the block's rows of a slot all read the same
    keys, and the ``num_attention_heads / num_key_value_heads`` query
    heads of a KV head share them). The block's own few rows are left
    out."""
    return live_tokens * kv_token_layer_bytes(m, itemsize) \
        * m["num_hidden_layers"]
