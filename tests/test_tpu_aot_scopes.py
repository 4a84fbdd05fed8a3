"""The TPU compiler's own output, without a chip: the paged serve
programs compiled for a described v5e. The decode program keeps every
instruction's ``op_name``, and the operations a trace will show
(fusions, copies, custom calls) land under the model's scopes; and no
paged program moves the KV pool — the write updates the donated pool in
place and the page gather reads it, nothing else touches pool-sized
bytes; and the sampler orders a vocabulary by ONE sort of its values,
with no permutation to gather through. The only test file that
describes a TPU topology (one process may hold libtpu: the
on-chip-measurement guide, section 2), and only inside fixtures."""
import math
import os
import re
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from mxtpu.models import llama, sambay
from mxtpu.telemetry import scopes as tscopes

# Mistral's head shapes (32 query / 8 kv heads of 128); depth, FFN and
# row length cut. 257 pages: a prime, so a shape that holds the pool or
# one layer's slab of it is known by that factor whatever XLA folds it
# into, and a slot's gathered rows (8 x 32 pages) never are.
SLOTS, PAGE, N_PAGES, LAYERS, BUCKET = 8, 16, 257, 4, 128


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


def _compile_all(lowered):
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return {name: low.compile() for name, low in lowered.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _lower_decode(family, cfg, one_chip):
    """``family.decode_slots_paged`` lowered for the chip, its state
    donated as the engine donates it. Returns (lowered, params, kv, sv)
    as shapes on the chip."""
    arg = partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: arg(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(partial(family.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    state = jax.eval_shape(
        lambda: family.init_paged_cache(cfg, SLOTS, N_PAGES, PAGE))
    per_slot = ("lengths", "tokens", "rngs")
    kv = on_chip({n: a for n, a in state.items() if n not in per_slot})
    sv = on_chip({n: state[n] for n in per_slot})
    decode = partial(family.decode_slots_paged, cfg)
    decode.__name__ = "decode_slots_paged"
    lowered = jax.jit(decode, donate_argnums=(1,)).lower(
        params, kv, sv, arg((SLOTS,), jnp.bool_),
        arg((SLOTS, cfg.max_seq_len // PAGE), jnp.int32),
        arg((SLOTS,), jnp.float32), arg((SLOTS,), jnp.int32),
        arg((SLOTS,), jnp.float32))
    return lowered, params, kv, sv


@pytest.fixture(scope="module")
def compiled(one_chip):
    """name -> compiled executable of ``decode_slots_paged``, one
    ``prefill_slot_paged`` bucket and ``copy_page``, pool donated as
    the engine donates it, for one v5e chip."""
    cfg = replace(llama.CONFIGS["tiny"], vocab_size=32768, dim=4096,
                  n_layers=LAYERS, n_heads=32, n_kv_heads=8,
                  hidden_dim=2048, max_seq_len=512, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    decode, params, kv, sv = _lower_decode(llama, cfg, one_chip)
    per_slot = cfg.max_seq_len // PAGE
    scalar = partial(arg, ())
    prefill = partial(llama.prefill_slot_paged, cfg)
    prefill.__name__ = "prefill_slot_paged"
    lowered = {
        "decode_slots_paged": decode,
        "prefill_slot_paged": jax.jit(prefill, donate_argnums=(6,)).lower(
            params, arg((1, BUCKET), jnp.int32), scalar(jnp.int32),
            scalar(jnp.int32), arg((per_slot,), jnp.int32),
            scalar(jnp.int32), kv, sv, arg((2,), jnp.uint32),
            scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.float32)),
        "copy_page": jax.jit(llama.copy_page, donate_argnums=(0,)).lower(
            kv, scalar(jnp.int32), scalar(jnp.int32)),
    }
    return _compile_all(lowered)


@pytest.fixture(scope="module")
def decode_text(compiled):
    return compiled["decode_slots_paged"].as_text()


@pytest.fixture(scope="module")
def sambay_decode_text(one_chip):
    """The second family's decode program at toy depth and width over
    Phi-4-mini-flash's 200064 rows: the sampler is ``llama``'s, at the
    vocabulary where its gathers cost 130 ms a step."""
    cfg = replace(sambay.CONFIGS["tiny"], vocab_size=200064, dim=512,
                  n_layers=8, n_heads=8, n_kv_heads=4, hidden_dim=512,
                  sliding_window=128, max_seq_len=512, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16)
    decode, *_ = _lower_decode(sambay, cfg, one_chip)
    return _compile_all({"decode": decode})["decode"].as_text()


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


# -- no paged program moves the pool ---------------------------------------
_COMPUTATION = re.compile(r"\s*(ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(
    r"\s*(ROOT\s+)?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)")
_SLAB = N_PAGES * PAGE * 8 * 128            # one layer of K (or V)


def _computations(text):
    """HLO text -> {computation: [(name, elements, opcode, rest, root)]}
    for its array-valued instructions (tuples move no bytes)."""
    out, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = out.setdefault(m.group(2), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            root, name, _, dims, opcode, rest = m.groups()
            n = math.prod(int(d) for d in dims.split(",")) if dims else 1
            cur.append((name, n, opcode, rest, bool(root)))
    return out


def _pool_sized(n):
    return n >= _SLAB and n % N_PAGES == 0


def _pool_movers(text):
    """The executed instructions (fusion bodies are their fusion's)
    whose result is the pool or a layer's slab of it, other than the
    in-place writes: a fusion rooted in a ``scatter`` or a
    ``dynamic-update-slice`` whose first operand is the pool itself and
    whose every other operand is smaller than a slab."""
    comps = _computations(text)
    bodies = {m.group(1) for instrs in comps.values()
              for _, _, opcode, rest, _ in instrs if opcode == "fusion"
              for m in [re.search(r"calls=%?([\w.\-]+)", rest)] if m}
    movers = []
    for comp, instrs in comps.items():
        if comp in bodies:
            continue
        sizes = {name: n for name, n, _, _, _ in instrs}
        for name, n, opcode, rest, _ in instrs:
            if not _pool_sized(n) or opcode in (
                    "parameter", "get-tuple-element", "bitcast"):
                continue
            if opcode == "fusion":
                body = comps[re.search(r"calls=%?([\w.\-]+)",
                                       rest).group(1)]
                root = next(op for _, _, op, _, is_root in body if is_root)
                operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
                if (root in ("scatter", "dynamic-update-slice")
                        and sizes.get(operands[0]) == n
                        and all(sizes.get(o, 0) < _SLAB
                                for o in operands[1:])):
                    continue
            movers.append(f"{comp}: {name} = {opcode}[{n} elements]")
    return movers


@pytest.mark.parametrize("program", ["decode_slots_paged",
                                     "prefill_slot_paged", "copy_page"])
def test_tpu_paged_programs_leave_the_pool_in_place(compiled, program):
    """No copy, slice, update-slice, select or loop fusion produces the
    pool or a slab of it: the write's scatter (``copy_page``: its
    one-page update-slice) updates the donated buffer where it lies.
    Stored head-major, or scanned as ``xs``/``ys``, XLA relays the pool
    out for the scatter and back, ten pool-sized operations a step."""
    exe = compiled[program]
    assert _pool_movers(exe.as_text()) == []
    # K and V both: the program never holds a second pool
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * LAYERS * _SLAB * 2
    assert mem.temp_size_in_bytes < LAYERS * _SLAB * 2, mem


def test_tpu_page_gather_selects_only_indices(decode_text):
    """The gather promises its bounds: under ``kv_gather`` nothing
    selects over gathered rows (``jnp.take``'s default fill was a
    ``select_n`` over every slot's whole row, 12 ms of an 85 ms step);
    what is left normalises the page table's own entries."""
    rows = SLOTS * (512 // PAGE)
    for line in decode_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(5) == "select" and "/kv_gather/" in line:
            dims = m.group(4)
            assert m.group(3) in ("s32", "pred") and math.prod(
                int(d) for d in dims.split(",")) <= rows, line


# -- the sampler sorts values, once, and gathers nothing -------------------
_IN_SAMPLER = re.compile(r'op_name="[^"]*[/(]sampler[/)]')
_SORT = re.compile(r" = (\(.*\)|\S+) sort\((.*?)\), dimensions=")


@pytest.mark.parametrize("program,rows,vocab", [
    ("decode_slots_paged", SLOTS, 32768), ("prefill_slot_paged", 1, 32768),
    ("sambay.decode_slots_paged", SLOTS, 200064)])
def test_tpu_sampler_sorts_values_once_and_gathers_nothing(
        request, program, rows, vocab):
    """Under the scope ``sampler`` the compiled program holds exactly
    one ``sort``, of one operand (the values; no ``iota`` rides along),
    and no ``gather`` as large as the logits: the kth value is one
    element a row. An ``argsort`` with ``take_along_axis`` compiled to
    two stable two-operand sorts and two ``rows x vocab`` gathers, 153
    ms of a 194 ms step at 200064 rows (PERF.md, PR 28)."""
    if program.startswith("sambay."):
        text = request.getfixturevalue("sambay_decode_text")
    else:
        text = request.getfixturevalue("compiled")[program].as_text()
    sorts, gathers = [], []
    for line in text.splitlines():
        if not _IN_SAMPLER.search(line):
            continue
        m = _SORT.search(line)
        if m:
            sorts.append((m.group(1), m.group(2).count("%")))
        m = _INSTRUCTION.match(line)
        if m and m.group(5) == "gather":
            gathers.append(math.prod(
                int(d) for d in m.group(4).split(",") if d))
    assert [n for _, n in sorts] == [1], sorts
    shape = sorts[0][0]
    assert shape.startswith("f32[") and not shape.startswith("("), shape
    assert math.prod(int(d) for d in re.match(
        r"f32\[([\d,]*)\]", shape).group(1).split(",")) == rows * vocab
    assert all(n <= rows for n in gathers), gathers
