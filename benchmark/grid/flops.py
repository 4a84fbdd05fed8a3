"""Operations and bytes computed from shapes: the numerators of
``train_mfu`` and ``flash_roofline_share``. Kept with the benchmark so
that no later PR can change what a share is a share of."""
from __future__ import annotations

from typing import Any, Dict


def params_per_layer(m: Dict[str, Any]) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    attn = d * hd * (2 * m["num_attention_heads"]
                     + 2 * m["num_key_value_heads"])
    return attn + 3 * d * m["intermediate_size"] + 2 * d


def param_count(m: Dict[str, Any]) -> int:
    """All parameters, embedding and untied head included."""
    return (m["num_hidden_layers"] * params_per_layer(m)
            + 2 * m["vocab_size"] * m["hidden_size"]
            + m["hidden_size"])


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward + backward operations a token of a ``seq``-long packed
    sequence needs: 6 per matmul parameter (the embedding is a gather
    and does not count; the head is a matmul and does), plus causal
    attention, 6·layers·hidden·seq (QK^T and PV, half the square,
    forward and twice that backward). Recomputation is not counted."""
    n_matmul = (m["num_hidden_layers"]
                * (params_per_layer(m) - 2 * m["hidden_size"])
                + m["vocab_size"] * m["hidden_size"])
    attn = 6 * m["num_hidden_layers"] * m["hidden_size"] * seq
    return 6.0 * n_matmul + attn


def flash_call(batch: int, heads: int, kv_heads: int, seq: int,
               hd: int, itemsize: int = 2) -> Dict[str, Dict[str, float]]:
    """One causal flash-attention call on (batch, heads, seq, hd)
    queries: the operations and the least bytes of its forward kernel
    and of its backward pass (dq and dk/dv together). Forward: QK^T
    and PV over the causal half, 2·2·b·h·s²·hd / 2. Backward: five
    matmuls of that size (recompute S, dV, dP, dQ, dK) = 2.5 × forward.
    Bytes: each operand read and each result written once (q, k, v, o
    forward; q, k, v, o, do read and dq, dk, dv written backward)."""
    f = 2.0 * batch * heads * seq * seq * hd
    q = batch * heads * seq * hd * itemsize
    kv = batch * kv_heads * seq * hd * itemsize
    return {"fwd": {"flops": f, "bytes": 2 * q + 2 * kv},
            "bwd": {"flops": 2.5 * f, "bytes": 4 * q + 4 * kv}}
