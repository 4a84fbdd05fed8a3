"""What the benchmark builds from the program for every driver: the
program's config object from a configuration file's published keys,
a key from ``--seed``, and the peak memory of the devices used."""
from __future__ import annotations


def llama_config(model: dict, run: dict):
    """The program's config object from the file's published keys."""
    import jax.numpy as jnp
    from mxtpu.models import llama
    return llama.LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        hidden_dim=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        attn_impl=run.get("attn_impl", "flash"),
        remat=bool(run.get("remat", True)),
        remat_policy=run.get("remat_policy"))


def seed_key(seed: int):
    """A raw threefry key from any whole number (``--seed`` can pass
    2**31, which ``PRNGKey`` of a 32-bit build would not take)."""
    import numpy as np
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
