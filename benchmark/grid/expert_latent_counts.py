"""Operations and bytes of a routed expert layer and of a latent (MLA)
decode attention, computed from shapes and counts: the numerators of
``moe_roofline_share`` and ``mla_decode_roofline_share``. They count
the work the algorithm needs whatever implements it, and are kept with
the benchmark (beside ``flops.py``) so that no later PR can change what
a share is a share of."""
from __future__ import annotations

from typing import Any, Dict


def expert_layers(m: Dict[str, Any]) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def expert_matrices_bytes(m: Dict[str, Any], itemsize: int = 2) -> int:
    """One routed expert's three matrices (gate, up, down)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def experts_step(m: Dict[str, Any], touched: float, assignments: float,
                 itemsize: int = 2) -> Dict[str, float]:
    """What the grouped products of ONE decode step (every expert
    layer) have to do: read the three matrices of every expert that got
    a token (``touched``: experts summed over the expert layers) once,
    and multiply each (token, expert) assignment through them, 2
    operations a weight. The tokens' own rows (a few hundred of 2048)
    are left out."""
    return {"bytes": touched * expert_matrices_bytes(m, itemsize),
            "flops": 2.0 * 3 * m["hidden_size"]
            * m["moe_intermediate_size"] * assignments}


def latent_row_bytes(m: Dict[str, Any], itemsize: int = 2) -> int:
    """A token's cache row in one layer: the latent and the shared
    rope key (512 + 64 values: 1,152 bytes in bf16), whatever padding
    the pool stores it with."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def latent_decode_step_bytes(m: Dict[str, Any], live_tokens: float,
                             itemsize: int = 2) -> float:
    """The least a decode step's attention reads: every live token's
    row once a layer (all heads share it, and the row is key and value
    at once)."""
    return live_tokens * latent_row_bytes(m, itemsize) \
        * m["num_hidden_layers"]
