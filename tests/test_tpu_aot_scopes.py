"""The scope map on the TPU compiler's own output, without a chip: the
decode program compiled for a described v5e keeps every instruction's
``op_name``, and the operations a trace will show (fusions, copies,
custom calls) land under the model's scopes. The only test file that
describes a TPU topology (one process may hold libtpu: the
on-chip-measurement guide, section 2), and only inside fixtures."""
import os
import re
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from mxtpu.models import llama
from mxtpu.telemetry import scopes as tscopes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def decode_text(one_chip):
    """``decode_slots_paged`` at Mistral's head shapes, two layers, for
    one v5e chip: the optimised HLO text."""
    cfg = replace(llama.CONFIGS["tiny"], vocab_size=32768, dim=1024,
                  n_layers=2, n_heads=8, n_kv_heads=2, hidden_dim=2048,
                  max_seq_len=512, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16)
    slots, page, n_pages = 8, 16, 129

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = on_chip(jax.eval_shape(partial(llama.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    state = jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, slots, n_pages, page))
    kv = on_chip({n: state[n] for n in ("k", "v")})
    sv = on_chip({n: state[n] for n in ("lengths", "tokens", "rngs")})
    fn = partial(llama.decode_slots_paged, cfg)
    fn.__name__ = "decode_slots_paged"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, kv, sv, arg((slots,), jnp.bool_),
            arg((slots, cfg.max_seq_len // page), jnp.int32),
            arg((slots,), jnp.float32), arg((slots,), jnp.int32),
            arg((slots,), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    return compiled.as_text()


def test_tpu_program_keeps_its_name_and_parses_fast(decode_text):
    t0 = time.perf_counter()
    module, scopes = tscopes.scope_map(decode_text)
    assert time.perf_counter() - t0 < 1.0
    assert module == "jit_decode_slots_paged"
    assert len(scopes) > 500


@pytest.mark.parametrize("scope", ["sampler", "kv_gather", "attention",
                                   "kv_write", "mlp", "norm", ""])
def test_tpu_fusions_land_under_the_model_scopes(decode_text, scope):
    """What the trace's "XLA Ops" line shows are fusions, copies and
    custom calls; each scope a reader follows must hold some."""
    _, scopes = tscopes.scope_map(decode_text)
    shown = {name: path.split("/")[0] for name, (path, _) in
             scopes.items() if re.search(r"fusion|^copy|custom-call", name)}
    assert scope in set(shown.values()), sorted(set(shown.values()))
    # the model's scopes and nothing else: no function's name leaks in
    assert set(shown.values()) <= {
        "", "embed", "norm", "qkv_proj", "rope", "kv_write", "kv_gather",
        "attention", "out_proj", "mlp", "lm_head", "sampler"}
