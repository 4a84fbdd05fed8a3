"""Operations and bytes of a Mamba-2 (SSD) layer's recurrence and of a
layer of non-gated (relu²) routed experts, computed from the published
keys and counts: the numerators of ``ssd_chunk_roofline_share``,
``ssm_state_roofline_share`` and ``relu2_moe_roofline_share``, and the
expert layers ``hybrid_moe_experts_touched_mean`` divides by. They
count the work the algorithm needs whatever implements it, and are kept
with the benchmark (beside ``flops.py`` and ``expert_latent_counts.py``)
so that no later PR can change what a share is a share of."""
from __future__ import annotations

from typing import Any, Dict


def ssm_layers(m: Dict[str, Any]) -> int:
    """Mamba-2 layers: the ``M`` of ``hybrid_override_pattern``."""
    return m["hybrid_override_pattern"].count("M")


def expert_layers(m: Dict[str, Any]) -> int:
    """Layers of routed experts: the ``E`` of ``hybrid_override_pattern``
    (interleaved with the other kinds, not after a run of dense layers as
    ``first_k_dense_replace`` counts them)."""
    return m["hybrid_override_pattern"].count("E")


def state_bytes(m: Dict[str, Any], itemsize: int = 4) -> int:
    """One slot's state in ONE layer: ``mamba_num_heads`` heads of
    ``mamba_head_dim x ssm_state_size``, float32 (64 x 64 x 128 x 4 =
    2.10 MB for Nemotron-3-Nano)."""
    return (m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
            * itemsize)


def decode_state_bytes(m: Dict[str, Any], slots_running: float,
                       itemsize: int = 4) -> float:
    """The least a decode step's recurrence moves: every running slot's
    state read once and written once in every Mamba layer (the decayed,
    updated state is a new value of every element; the read-out rides
    on the same pass)."""
    return 2.0 * slots_running * state_bytes(m, itemsize) * ssm_layers(m)


def ssd_chunk(m: Dict[str, Any], tokens: int, itemsize: int = 2
              ) -> Dict[str, float]:
    """What the chunked SSD of ``tokens`` positions (whole chunks of
    ``chunk_size`` Q) does over every Mamba layer. Operations, 2 a
    multiply-add, a chunk: ``C B^T`` a group (G Q Q n), its masked
    product with ``dt x`` a head (H Q Q p), the chunk's state (H Q p n)
    and the carried state's read-out (H Q p n). Bytes: x, B and C in the
    activations' type, dt in float32, y out in the activations' type,
    and the float32 state in and out once."""
    Q, n = m["chunk_size"], m["ssm_state_size"]
    H, p, G = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"]
    chunks = tokens / Q
    flops = 2.0 * chunks * (G * Q * Q * n + H * Q * Q * p + 2 * H * Q * p * n)
    moved = (tokens * (2 * H * p + 2 * G * n) * itemsize + tokens * H * 4
             + 2 * state_bytes(m))
    L = ssm_layers(m)
    return {"flops": L * flops, "bytes": L * moved}


def relu2_expert_bytes(m: Dict[str, Any], itemsize: int = 2) -> int:
    """One routed non-gated expert's TWO matrices (up, down)."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"] * itemsize


def relu2_experts_step(m: Dict[str, Any], touched: float, assignments: float,
                       itemsize: int = 2) -> Dict[str, float]:
    """What the grouped products of ONE decode step (every expert layer)
    have to do: read the two matrices of every expert that got a token
    (``touched``: experts summed over the expert layers) once, and
    multiply each (token, expert) assignment through them, 2 operations
    a weight. ``expert_latent_counts.experts_step`` with two matrices
    where it counts three."""
    return {"bytes": touched * relu2_expert_bytes(m, itemsize),
            "flops": 2.0 * 2 * m["hidden_size"]
            * m["moe_intermediate_size"] * assignments}
