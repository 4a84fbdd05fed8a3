"""Bytes of a power-retention state, computed from the published keys:
the numerator of ``retention_decode_roofline_share``. They count what
the algorithm holds whatever lays it out (the program stores 8320 rows
a KV head where 8256 are distinct: ``mxtpu.ops.retention.sympow2``), and
are kept with the benchmark (beside ``flops.py`` and
``expert_latent_counts.py``) so that no later PR can change what a
share is a share of."""
from __future__ import annotations

from typing import Any, Dict


def feature_rows(m: Dict[str, Any]) -> int:
    """Distinct products of the symmetric degree-2 feature map of a
    head: ``d (d + 1) / 2`` (8256 at head_dim 128)."""
    d = m["head_dim"]
    return d * (d + 1) // 2


def state_bytes(m: Dict[str, Any], itemsize: int = 4) -> int:
    """One slot's state in ONE layer: a KV head holds ``S`` (rows x
    head_dim) and ``z`` (rows), float32: 8 x 8256 x 129 x 4 = 34.08 MB
    for Brumby-14B."""
    return (m["num_key_value_heads"] * feature_rows(m)
            * (m["head_dim"] + 1) * itemsize)


def decode_step_bytes(m: Dict[str, Any], slots_running: float,
                      itemsize: int = 4) -> float:
    """The least a decode step's retention moves: every running slot's
    state read once and written once in every layer (the decayed,
    updated state is a new value of every element; the read-out can
    ride on either pass)."""
    return 2.0 * slots_running * state_bytes(m, itemsize) \
        * m["num_hidden_layers"]
