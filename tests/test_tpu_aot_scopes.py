"""The TPU compiler's own output, without a chip: the paged serve
programs compiled for a described v5e. The decode program keeps every
instruction's ``op_name``, and the operations a trace will show
(fusions, copies, custom calls) land under the model's scopes; and no
paged program moves the KV pool — the write updates the donated pool in
place and the attention reads it (the plain step through ONE Pallas
kernel call a layer that walks live pages, so that no gathered row
exists; the verify step through the page gather), nothing else touches
pool-sized bytes; and the sampler builds no order of a vocabulary: no
``sort`` and no permutation to gather through, its two thresholds come
from ONE call of the search kernel. Code that asks
``jax.default_backend()`` here still sees the CPU (section 2 of the
guide), and the rule that picks the kernel asks, so the fixtures that
lower llama's programs answer ``"tpu"`` for it while they trace, as the
chip these compile for will. The only test file that
describes a TPU topology (one process may hold libtpu: the
on-chip-measurement guide, section 2), and only inside fixtures."""
import base64
import contextlib
import hashlib
import json
import math
import os
import re
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from mxtpu.models import latent_moe, llama, nemotron_h, retention, sambay
from mxtpu.ops.retention import STEP_KERNEL_NAME
from mxtpu.telemetry import scopes as tscopes

# Mistral's head shapes (32 query / 8 kv heads of 128); depth, FFN and
# row length cut. 1031 pages: a prime, so a shape that holds the pool or
# one layer's slab of it is known by that factor whatever XLA folds it
# into, and a slot's gathered rows (8 x 32 pages) never are; and K's
# pool (135 MB) is more than the chip's 128 MiB of VMEM, as every
# deployment's is (at 257 pages XLA staged the 34 MB pool in VMEM for
# the write and copied it back out for the kernel, which reads HBM).
SLOTS, PAGE, N_PAGES, LAYERS, BUCKET = 8, 16, 1031, 4, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_all(lowered):
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return {name: low.compile() for name, low in lowered.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@contextlib.contextmanager
def _as_on_a_tpu():
    """While it is entered, ``jax.default_backend()`` answers ``"tpu"``
    to the program's own code, as it will on the chip this compiles
    for."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield


def _lower_decode(family, cfg, one_chip, drafts=None):
    """``family.decode_slots_paged`` (with ``drafts`` a slot:
    ``decode_slots_spec``, the verify step) lowered for the chip, its
    state donated as the engine donates it. Returns (lowered, params,
    kv, sv) as shapes on the chip."""
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
    name = "decode_slots_spec" if drafts else "decode_slots_paged"
    decode = partial(getattr(family, name), cfg)
    decode.__name__ = name
    with _as_on_a_tpu():
        lowered = jax.jit(decode, donate_argnums=(1,)).lower(
            params, kv, sv, arg((SLOTS,), jnp.bool_),
            arg((SLOTS, cfg.max_seq_len // PAGE), jnp.int32),
            *([arg((SLOTS, drafts), jnp.int32)] if drafts else []),
            arg((SLOTS,), jnp.float32), arg((SLOTS,), jnp.int32),
            arg((SLOTS,), jnp.float32))
    return lowered, params, kv, sv


@pytest.fixture(scope="module")
def compiled(one_chip):
    """name -> compiled executable of ``decode_slots_paged``,
    ``decode_slots_spec`` (the verify step, three drafts a slot), one
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
    with _as_on_a_tpu():
        prefill = jax.jit(prefill, donate_argnums=(6,)).lower(
            params, arg((1, BUCKET), jnp.int32), scalar(jnp.int32),
            scalar(jnp.int32), arg((per_slot,), jnp.int32),
            scalar(jnp.int32), kv, sv, arg((2,), jnp.uint32),
            scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.float32))
    lowered = {
        "decode_slots_paged": decode,
        "decode_slots_spec": _lower_decode(llama, cfg, one_chip,
                                           drafts=3)[0],
        "prefill_slot_paged": prefill,
        "copy_page": jax.jit(llama.copy_page, donate_argnums=(0,)).lower(
            kv, scalar(jnp.int32), scalar(jnp.int32)),
    }
    return _compile_all(lowered)


@pytest.fixture(scope="module")
def decode_text(compiled):
    return compiled["decode_slots_paged"].as_text()


@pytest.fixture(scope="module")
def sambay_decode(one_chip):
    """The second family's decode program at toy depth and width (two
    Mamba+window pairs, layers "4/5", ONE GMU+cross pair; 8 query heads
    over 2 paired KV heads of 128 lanes, a 256-wide row) over
    Phi-4-mini-flash's 200064 rows: the sampler is ``llama``'s, at the
    vocabulary where its gathers cost 130 ms a step."""
    cfg = replace(sambay.CONFIGS["tiny"], vocab_size=200064, dim=512,
                  n_layers=8, n_heads=8, n_kv_heads=4, hidden_dim=512,
                  sliding_window=128, max_seq_len=512, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16)
    decode, *_ = _lower_decode(sambay, cfg, one_chip)
    return _compile_all({"decode": decode})["decode"]


@pytest.fixture(scope="module")
def sambay_decode_text(sambay_decode):
    return sambay_decode.as_text()


def test_tpu_program_keeps_its_name_and_parses_fast(decode_text):
    t0 = time.perf_counter()
    module, scopes = tscopes.scope_map(decode_text)
    assert time.perf_counter() - t0 < 1.0
    assert module == "jit_decode_slots_paged"
    assert len(scopes) > 500


def test_tpu_catalog_counts_kernels_and_fast_memory(compiled):
    """What the catalog keeps of the built program beside its scopes
    (``telemetry.programs()``): the Mosaic calls are the two Pallas
    kernels the decode step holds (the attention's walk over live pages
    in the layer loop, the sampler's threshold search), and XLA placed
    some of its buffers in the chip's fast memory (``S(1)`` in a
    result's layout), fewer than the text says ``S(1)`` (a fusion's
    inside names its operands and its root again) and within VMEM each.
    ``copy_page`` holds no kernel."""
    exe = compiled["decode_slots_paged"]
    prog = tscopes.register("aot_decode", exe, temp_bytes=1.0)
    text = exe.as_text()
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert prog.custom_calls == len(kernels) == 2
    assert 0 < prog.fast_mem_buffers < text.count("S(1)")
    assert 0 < prog.fast_mem_bytes <= prog.fast_mem_buffers * 128 * 2 ** 20
    assert (prog.module, prog.temp_bytes) == ("jit_decode_slots_paged", 1)
    assert prog.scopes == tscopes.scope_map(text)[1]
    copy = tscopes.register("aot_copy_page", compiled["copy_page"])
    assert copy.custom_calls == 0


@pytest.mark.parametrize("scope", ["sampler", "attention",
                                   "kv_write", "mlp", "norm", ""])
def test_tpu_fusions_land_under_the_model_scopes(decode_text, scope):
    """What the trace's "XLA Ops" line shows are fusions, copies and
    custom calls; each scope a reader follows must hold some."""
    from mxtpu.ops.paged_attention import KERNEL_NAME
    _, scopes = tscopes.scope_map(decode_text)
    shown = {name: path.split("/")[0] for name, (path, _) in
             scopes.items() if re.search(
                 rf"fusion|^copy|custom-call|^{KERNEL_NAME}", name)}
    assert scope in set(shown.values()), sorted(set(shown.values()))
    # the model's scopes and nothing else: no function's name leaks in
    assert set(shown.values()) <= {
        "", "embed", "norm", "qkv_proj", "rope", "kv_write", "kv_gather",
        "attention", "out_proj", "mlp", "lm_head", "sampler"}


# -- no paged program moves the pool ---------------------------------------
_IN_SAMPLER = re.compile(r'op_name="[^"]*[/(]sampler[/)]')
_COMPUTATION = re.compile(r"\s*(ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(
    r"\s*(ROOT\s+)?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)")
_SLAB = N_PAGES * PAGE * 8 * 128            # one layer of K (or V)


def _computations(text):
    """HLO text -> {computation: [(name, elements, opcode, rest, root,
    last dimension)]} for its array-valued instructions (tuples move no
    bytes)."""
    out, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = out.setdefault(m.group(2), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            root, name, _, dims, opcode, rest = m.groups()
            dims = [int(d) for d in dims.split(",") if d]
            cur.append((name, math.prod(dims), opcode, rest, bool(root),
                        dims[-1] if dims else 1))
    return out


_NOT_RUN = ("parameter", "get-tuple-element", "bitcast")


def _executed(comps):
    """{computation: instructions} without the fusions' bodies (they
    are their fusion's) and the computations nothing names."""
    bodies = {m.group(1) for instrs in comps.values()
              for _, _, opcode, rest, *_ in instrs if opcode == "fusion"
              for m in [re.search(r"calls=%?([\w.\-]+)", rest)] if m}
    return {comp: instrs for comp, instrs in comps.items()
            if comp not in bodies}


def _pool_sized(n):
    return n >= _SLAB and n % N_PAGES == 0


def _pool_movers(text):
    """The executed instructions (fusion bodies are their fusion's)
    whose result is the pool or a layer's slab of it, other than the
    in-place writes: a fusion rooted in a ``scatter`` or a
    ``dynamic-update-slice`` whose first operand is the pool itself and
    whose every other operand is smaller than a slab."""
    comps = _computations(text)
    movers = []
    for comp, instrs in _executed(comps).items():
        sizes = {name: n for name, n, *_ in instrs}
        for name, n, opcode, rest, *_ in instrs:
            if not _pool_sized(n) or opcode in _NOT_RUN:
                continue
            if opcode == "fusion":
                body = comps[re.search(r"calls=%?([\w.\-]+)",
                                       rest).group(1)]
                root = next(op for _, _, op, _, is_root, _ in body
                            if is_root)
                operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
                if (root in ("scatter", "dynamic-update-slice")
                        and sizes.get(operands[0]) == n
                        and all(sizes.get(o, 0) < _SLAB
                                for o in operands[1:])):
                    continue
            movers.append(f"{comp}: {name} = {opcode}[{n} elements]")
    return movers


@pytest.mark.parametrize("program", ["decode_slots_paged",
                                     "prefill_slot_paged", "copy_page",
                                     "decode_slots_spec"])
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


_ROW = SLOTS * 512 * 8 * 128        # every slot's whole row of K (or V)


def _rows(text, elements=_ROW, lanes=(128,)):
    """The executed instructions whose result is as large as all the
    slots' capacity-long rows of one layer's K and ends in a head's 128
    lanes: the gathered copy ``[8, 8, 512, 128]``, its token-major twin
    ``[256, 16, 8, 128]``, or either cut into key blocks. (A layer's
    ``wk`` is as large, and ends in 1024 or 4096.)"""
    return [f"{comp}: {name} = {opcode}"
            for comp, instrs in _executed(_computations(text)).items()
            for name, n, opcode, _, _, last in instrs
            if n == elements and last in lanes and opcode not in _NOT_RUN]


def test_tpu_decode_attention_is_one_kernel_call_over_live_pages(
        compiled):
    """The plain step's attention is ONE Pallas call in the layer
    loop's body, under ``attention``, fed by the pool itself; nothing
    under ``kv_gather`` is left, and no operation of the program
    produces a capacity-long row of every slot, in either layout (the
    gather wrote one for K and one for V, 268 MB each at the chat
    cell's shapes, and the key-block reads read them back)."""
    from mxtpu.ops.paged_attention import KERNEL_NAME
    text = compiled["decode_slots_paged"].as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line
             and not _IN_SAMPLER.search(line)]
    assert len(calls) == 1, calls
    assert re.search(rf"%{KERNEL_NAME}[.\d]* = ", calls[0]), calls[0]
    assert re.search(r'op_name="[^"]*/while/body/[^"]*/attention/'
                     + KERNEL_NAME, calls[0]), calls[0]
    assert "/kv_gather/" not in text
    assert _rows(text) == []
    # the same reading finds the verify step's rows, which still gather
    assert _rows(compiled["decode_slots_spec"].as_text())


def test_tpu_sambay_reads_its_shared_pool_through_the_rows_kernel(
        sambay_decode):
    """The second family's eight reads of its one shared pool: the full
    layer's is ONE call of the rows kernel under ``attention``, the
    cross layers' ONE call in their scan's body under
    ``cross_attention`` (seven trips at Phi-4-mini-flash's depth, one
    here), each fed by the pools as they are stored. Nothing under
    ``kv_gather`` is left, no operation produces every slot's
    capacity-long rows, token-major ``[8, 512, 256]`` or head-major
    ``[8, 2, 512, 128]`` (the gather wrote both, and the reads relaid
    them out four times more: 2.62 GB of temporaries at the agent
    cell's shapes, 0.09 GB now), and the donated pools stay in place."""
    from mxtpu.ops.paged_attention import ROWS_KERNEL_NAME
    text = sambay_decode.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line
             and not _IN_SAMPLER.search(line)]
    assert len(calls) == 2, calls
    assert all(re.search(rf"%{ROWS_KERNEL_NAME}[.\d]* = ", c)
               for c in calls), calls
    scopes = sorted(re.search(r'op_name="([^"]*)"', c).group(1)
                    for c in calls)
    assert scopes[0].startswith("jit(decode_slots_paged)/attention/"
                                + ROWS_KERNEL_NAME), scopes
    assert re.search(rf"/while/body/([^/]*/)?cross_attention/"
                     rf"{ROWS_KERNEL_NAME}", scopes[1]), scopes
    pool = f"bf16[1,{N_PAGES},{PAGE},256]"
    assert all(c.split("operand_layout_constraints")[1].count(pool) == 2
               for c in calls), calls
    assert "/kv_gather/" not in text
    row = SLOTS * 512 * 256
    assert _rows(text, row, (128, 256)) == []
    mem = sambay_decode.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * N_PAGES * PAGE * 256 * 2


def test_tpu_page_gather_selects_only_indices(compiled):
    """The gather promises its bounds: under ``kv_gather`` nothing
    selects over gathered rows (``jnp.take``'s default fill was a
    ``select_n`` over every slot's whole row, 12 ms of an 85 ms step);
    what is left normalises the page table's own entries. Read off the
    verify step, the llama program that still gathers on a TPU."""
    text = compiled["decode_slots_spec"].as_text()
    assert "/kv_gather/" in text
    rows = SLOTS * (512 // PAGE)
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(5) == "select" and "/kv_gather/" in line:
            dims = m.group(4)
            assert m.group(3) in ("s32", "pred") and math.prod(
                int(d) for d in dims.split(",")) <= rows, line


# -- the sampler searches: no sort, no gather; the kernel in decode alone ----
@pytest.mark.parametrize("program,rows,vocab", [
    ("decode_slots_paged", SLOTS, 32768), ("prefill_slot_paged", 1, 32768),
    ("sambay.decode_slots_paged", SLOTS, 200064),
    ("latent_moe.decode_slots_paged", SLOTS, 32768),
    ("retention.decode_slots_paged", SLOTS, 32768)])
def test_tpu_sampler_searches_and_neither_sorts_nor_gathers(
        request, program, rows, vocab):
    """Under the scope ``sampler`` the compiled program holds NO
    ``sort`` (from PR 28 to PR 33 it held one, of the values: the
    costliest operation of a decode step at 200064 rows, 3.9 ms; before
    that two stable sorts with an ``iota`` and two ``rows x vocab``
    gathers, 153 ms) and no ``gather`` larger than one element a row. A
    decode program holds ONE call of the threshold search's kernel
    (``ops.threshold``), fed the whole block of rows padded to eight
    (both thresholds of every row come out of it; its VMEM request fits
    at 200064 rows). A prefill program samples ONE row and holds no
    kernel at all: fewer than eight rows take the ``jnp`` search, so the
    kernel enters no program that did not hold one already (chat's four
    buckets paid 10.6 s of warm set-up for it: PERF.md, PR 34-35)."""
    # imported here: ``aot_instruction_lists.py`` compiles these fixtures
    # over a parent tree too, which has no such module
    from mxtpu.ops.threshold import KERNEL_NAME as SAMPLER_KERNEL_NAME
    fixture, _, name = program.rpartition(".")
    if fixture == "sambay":
        text = request.getfixturevalue("sambay_decode_text")
    elif fixture:
        text = request.getfixturevalue(fixture + "_decode")[2].as_text()
    else:
        text = request.getfixturevalue("compiled")[name].as_text()
    sorts, gathers, calls = [], [], []
    for line in text.splitlines():
        if "tpu_custom_call" in line and "custom-call(" in line \
                and SAMPLER_KERNEL_NAME in line:
            assert _IN_SAMPLER.search(line), line
        if not _IN_SAMPLER.search(line):
            continue
        if re.search(r" sort\(", line):
            sorts.append(line)
        m = _INSTRUCTION.match(line)
        if m and m.group(5) == "gather":
            gathers.append(math.prod(
                int(d) for d in m.group(4).split(",") if d))
        if "tpu_custom_call" in line and "custom-call(" in line:
            calls.append(line)
    assert not sorts, sorts
    assert all(n <= rows for n in gathers), gathers
    if rows < 8:
        assert not calls, calls
        return
    assert len(calls) == 1, calls
    assert re.search(rf"%{SAMPLER_KERNEL_NAME}[.\d]* = ", calls[0]), calls
    padded = -(-rows // 8) * 8
    assert f"f32[{padded},{vocab}]" in calls[0].split(
        "operand_layout_constraints")[1], calls[0]


# -- the latent-attention, routed-expert family -------------------------------
@pytest.fixture(scope="module")
def latent_moe_decode(one_chip):
    """``latent_moe.decode_slots_paged`` at the published attention and
    expert widths (32 heads of 128 + 64, a 512 + 64 row stored 640 wide,
    128 experts of 2048 x 768 top-6), one dense and seven expert
    layers, a 32768-row vocabulary: a 169 MB pool (more than the chip's
    VMEM) and a 4.2 GB expert bank, as shapes."""
    cfg = latent_moe.LatentMoEConfig(n_layers=8, vocab_size=32768,
                                     max_seq_len=512)
    decode, _, kv, _ = _lower_decode(latent_moe, cfg, one_chip)
    return cfg, kv, _compile_all({"decode": decode})["decode"]


def test_tpu_latent_decode_leaves_the_pool_and_the_bank_in_place(
        latent_moe_decode):
    """The one-token write updates the donated pool where it lies, and
    the expert bank reaches the grouped products as it is stored: the
    program's temporaries are the step's activations (no gathered
    row exists: the attention kernel reads the pool where it lies). A 576-wide row is laid out page-minor by the v5e and
    copied whole around the write (1.68 GB at the benchmark's size); a
    layer's slab of the bank cut out by the layer scan is 600 MB."""
    cfg, kv, exe = latent_moe_decode
    pool = math.prod(kv["latent"].shape) * 2
    assert kv["latent"].shape == (8, N_PAGES, PAGE, 640)
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    slab = cfg.n_routed_experts * cfg.dim * cfg.moe_hidden_dim * 2
    assert mem.temp_size_in_bytes < min(pool, slab) / 2, mem
    # three grouped products (the Pallas kernel ``gmm``), each over the
    # whole stack's 7 x 128 groups
    text = exe.as_text()
    calls = [ln for ln in text.splitlines()
             if re.match(r"\s*%?gmm[.\d]* = ", ln)]
    assert len(calls) == 3, len(calls)
    assert all("tpu_custom_call" in ln for ln in calls)
    assert sum("bf16[896,2048,768]" in ln for ln in calls) == 2
    assert sum("bf16[896,768,2048]" in ln for ln in calls) == 1


@pytest.mark.parametrize("scope", ["moe_router", "moe_dispatch",
                                   "moe_experts", "moe_shared",
                                   "mla_attention", "kv_write", "sampler"])
def test_tpu_latent_decode_operations_land_under_its_scopes(
        latent_moe_decode, scope):
    """As for llama's program; the grouped products are the kernel
    ``gmm`` under ``moe_experts``, the attention ONE call a layer of the
    kernel that walks the latent pool's live pages, under
    ``mla_attention``, and nothing is gathered."""
    from mxtpu.ops.paged_attention import LATENT_KERNEL_NAME
    _, scopes = tscopes.scope_map(latent_moe_decode[2].as_text())
    shown = {name: path.split("/")[0] for name, (path, _) in
             scopes.items() if re.search(
                 rf"fusion|^copy|custom-call|^gmm|^{LATENT_KERNEL_NAME}",
                 name)}
    walks = [n for n in shown if n.startswith(LATENT_KERNEL_NAME)]
    assert [shown[n] for n in walks] == 2 * ["mla_attention"]
    assert "kv_gather" not in set(shown.values())
    assert scope in set(shown.values()), sorted(set(shown.values()))
    assert set(shown.values()) <= {
        "", "embed", "norm", "qkv_proj", "rope", "kv_write", "kv_gather",
        "mla_attention", "out_proj", "mlp", "lm_head", "sampler",
        "moe_router", "moe_dispatch", "moe_experts", "moe_shared"}
    assert {shown[n] for n in shown if n.startswith("gmm")} == {
        "moe_experts"}


# -- the power-retention family ---------------------------------------------------
@pytest.fixture(scope="module")
def retention_decode(one_chip):
    """``retention.decode_slots_paged`` at the published head shapes (40
    query heads over 8 KV heads of 128: 8320 stored rows a head), four
    layers, the SwiGLU and the vocabulary cut: 8 slots' state is 1.1 GB
    (34.35 MB a slot and layer), as shapes."""
    cfg = retention.RetentionConfig(n_layers=4, vocab_size=32768,
                                    hidden_dim=2048, max_seq_len=512)
    decode, _, kv, _ = _lower_decode(retention, cfg, one_chip)
    return cfg, kv, _compile_all({"decode": decode})["decode"]


def test_tpu_retention_decode_updates_the_state_where_it_lies(
        retention_decode):
    """The decayed, updated state is written into the donated bank in
    place by the step's Pallas kernel (``ops.retention``: one read and
    one write of a layer's state): the program's temporaries are the
    step's activations, far under one layer's slice of the state (275
    MB here)."""
    cfg, kv, exe = retention_decode
    assert kv["S"].shape == (4, SLOTS, 8, 128, 8320)
    assert kv["z"].shape == (4, SLOTS, 8, 8320)
    assert {a.dtype for a in kv.values()} == {jnp.dtype(jnp.float32)}
    state = sum(math.prod(a.shape) * 4 for a in kv.values())
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state / cfg.n_layers / 4, mem
    # no copy of a layer's slice, or of the bank, is executed
    # ONE call of the step's kernel (the layer loop's), which takes the
    # loop-carried bank itself and hands it back aliased
    text = exe.as_text()
    calls = [ln for ln in text.splitlines() if re.match(
        rf"\s*%?{STEP_KERNEL_NAME}[.\d]* = ", ln)]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert "f32[4,8,8,128,8320]" in calls[0].split("custom-call(")[0]
    slice_ = SLOTS * 8 * 8320 * 128
    copies = [name for instrs in _executed(
        _computations(exe.as_text())).values()
        for name, n, opcode, *_ in instrs
        if opcode == "copy" and n >= slice_]
    assert not copies, copies


@pytest.mark.parametrize("scope", ["retention_state", "retention_gate",
                                   "qkv_proj", "out_proj", "mlp", "sampler"])
def test_tpu_retention_decode_operations_land_under_its_scopes(
        retention_decode, scope):
    """As for llama's program: what a trace will show of the step lands
    under the family's scopes; nothing is written, gathered or attended
    as keys and values."""
    _, scopes = tscopes.scope_map(retention_decode[2].as_text())
    shown = {name: path.split("/")[0] for name, (path, _) in
             scopes.items() if re.search(
                 rf"fusion|^copy|custom-call|^{STEP_KERNEL_NAME}", name)}
    assert {shown[n] for n in shown if n.startswith(STEP_KERNEL_NAME)} == {
        "retention_state"}
    assert scope in set(shown.values()), sorted(set(shown.values()))
    assert set(shown.values()) <= {
        "", "embed", "norm", "qkv_proj", "rope", "retention_gate",
        "retention_state", "out_proj", "mlp", "lm_head", "sampler"}


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)],
                         ids=["gate_up", "down"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_tpu_grouped_product_fits_vmem_in_both_types(one_chip, dtype, k, n):
    """``megablox.gmm`` at the routed experts' published widths, a
    decode step's 192 rows over the stack's 7 x 128 groups, compiles
    for a v5e in bfloat16 (the rag cell) and in float32
    (``chip_smoke.py``'s second pass): a float32 group's whole matrix as
    one tile is 6.3 MB, and double-buffered beside its rows and its
    accumulator it ran out of VMEM (every replica of that pass died in
    its first decode step), so a tile is sized in bytes."""
    from mxtpu.parallel import moe
    arg = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    exe = _compile_all({"gmm": jax.jit(partial(
        moe.grouped_matmul_kernel, tm=192)).lower(
            arg((192, k), dtype), arg((896, k, n), dtype),
            arg((896,), jnp.int32))})["gmm"]
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("transposed,k,n", [(True, 2688, 1856),
                                             (False, 1856, 2688)],
                         ids=["up_t", "down"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_tpu_non_gated_products_fit_vmem_in_both_types(one_chip, dtype,
                                                       transposed, k, n):
    """``megablox.gmm`` at Nemotron-H's expert widths, a decode step's
    192 rows over 4 x 128 groups: the up matrix stored (f, d) and read
    transposed, the down one (f, d) as it is; 1856 is not whole lane
    tiles, so a tile is the widest whole-lane divisor of the other side
    that keeps it under ``_GMM_TILE_BYTES``."""
    from mxtpu.parallel import moe
    arg = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    shape = (512, n, k) if transposed else (512, k, n)
    exe = _compile_all({"gmm": jax.jit(partial(
        moe.grouped_matmul_kernel, tm=192, transposed=transposed)).lower(
            arg((192, k), dtype), arg(shape, dtype),
            arg((512,), jnp.int32))})["gmm"]
    assert "tpu_custom_call" in exe.as_text()


# -- the Mamba-2, relu²-expert hybrid ------------------------------------------------
@pytest.fixture(scope="module")
def nemotron_decode(one_chip):
    """``nemotron_h.decode_slots_paged`` at the published widths (Mamba-2:
    64 heads of 64, 8 groups of state 128; relu² experts 2688 x 1856 top-6
    and a shared one of 3712; 32 query heads over 2 KV heads of 128), the
    pattern's first period ``MEMEM*EME``, 16 experts and a 32768-row
    vocabulary: 8 slots' Mamba state is 67 MB, an expert layer's up
    matrices 160 MB, as shapes."""
    cfg = replace(nemotron_h.NemotronHConfig(), pattern="MEMEM*EME",
                  n_routed_experts=16, vocab_size=32768, max_seq_len=512)
    decode, _, kv, _ = _lower_decode(nemotron_h, cfg, one_chip)
    return cfg, kv, _compile_all({"decode": decode})["decode"]


def test_tpu_nemotron_decode_leaves_the_state_and_the_bank_in_place(
        nemotron_decode):
    """The step reads and writes the Mamba-2 state in the donated bank
    where it lies, and the expert bank reaches the grouped products as it
    is stored: no state-sized and no bank-sized temporary. An (E, 2688,
    1856) up matrix is laid out 2688-minor by the chip, and the products'
    row-major operand was a copy of the whole bank (4.9 GB at the cell's
    size), so it is stored (E, 1856, 2688) and read transposed."""
    cfg, kv, exe = nemotron_decode
    assert kv["ssm"].shape == (4, SLOTS, 64, 64, 128)
    assert kv["ssm"].dtype == jnp.float32
    assert kv["k"].shape == (1, N_PAGES, PAGE, 256)
    state = sum(math.prod(a.shape) * a.dtype.itemsize for a in kv.values())
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= state
    up = cfg.n_routed_experts * cfg.dim * cfg.moe_hidden_dim * 2
    layer_state = math.prod(kv["ssm"].shape[1:]) * 4
    assert mem.temp_size_in_bytes < min(layer_state, up / 2), mem
    text = exe.as_text()
    calls = [ln for ln in text.splitlines()
             if re.match(r"\s*%?gmm[.\d]* = ", ln)]
    assert len(calls) == 8 and all("tpu_custom_call" in ln for ln in calls)
    assert sum("bf16[64,1856,2688]" in ln for ln in calls) == 8


@pytest.mark.parametrize("scope", ["ssm", "ssm/ssm_state", "moe_router",
                                   "moe_dispatch", "moe_experts",
                                   "moe_shared", "attention", "kv_write",
                                   "sampler"])
def test_tpu_nemotron_decode_operations_land_under_its_scopes(
        nemotron_decode, scope):
    """As for llama's program: what a trace will show of the step lands
    under the family's scopes; the recurrence under ``ssm_state`` inside
    ``ssm``, the grouped products under ``moe_experts``, the one walk of
    the attention layer's live pages under ``attention``, nothing
    gathered."""
    from mxtpu.ops.paged_attention import ROWS_KERNEL_NAME
    _, scopes = tscopes.scope_map(nemotron_decode[2].as_text())
    top = lambda path: "/".join(path.split("/")[:2 if path.startswith(
        "ssm/") else 1])
    shown = {name: top(path) for name, (path, _) in
             scopes.items() if re.search(
                 rf"fusion|^copy|custom-call|^gmm|^{ROWS_KERNEL_NAME}",
                 name)}
    walks = [n for n in shown if n.startswith(ROWS_KERNEL_NAME)]
    assert [shown[n] for n in walks] == ["attention"]
    assert "kv_gather" not in set(shown.values())
    assert scope in set(shown.values()), sorted(set(shown.values()))
    assert {shown[n] for n in shown if n.startswith("gmm")} == {
        "moe_experts"}


# ---------------------------------------------------------------------------
# the train step under each remat plan, FSDP over the four described chips
# ---------------------------------------------------------------------------
with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "grid", "configs",
        "mistral-7b-v0.3-d8-train.json")) as _f:
    # how the train cell's readers find the Pallas kernels in a trace
    FLASH_KERNELS = json.load(_f)["programs"]["flash_kernels"]
TRAIN_PLANS = ((), ("mlp_gate", "mlp_up"),
               ("mlp_gate", "mlp_up", "attn_out"),
               ("mlp_gate", "mlp_up", "attn_out", "attn_qkv"))


@pytest.fixture(scope="module")
def train_steps(topo):
    """plan -> (compiled ``train_step``, its catalog entry) at two
    layers, Mistral's head shapes and cut widths, 2 x 512 tokens a chip,
    the state sharded four ways as the train cell's is; the plan
    forced."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxtpu.parallel import step as pstep
    from mxtpu.parallel.sharding import batch_spec
    cfg = llama.LlamaConfig(vocab_size=8192, dim=1024, n_layers=2,
                            n_heads=8, n_kv_heads=2, hidden_dim=2048,
                            max_seq_len=512, dtype=jnp.bfloat16)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1, 1, 1, 1),
                ("dp", "fsdp", "pp", "ep", "sp", "tp"))
    rules, tx = llama.sharding_rules(cfg), optax.adamw(3e-4)

    def on(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)
    shapes = jax.eval_shape(partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    params = on(shapes, jax.tree.map(
        lambda s: NamedSharding(mesh, s), rules.tree_specs(shapes),
        is_leaf=lambda s: isinstance(s, P)))
    opt = on(jax.eval_shape(tx.init, shapes),
             pstep.opt_state_shardings(tx, shapes, mesh, rules))
    state = pstep.TrainState(params, opt, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=NamedSharding(mesh, P())), ())
    batch = {"tokens": jax.ShapeDtypeStruct(
        (8, 512), jnp.int32, sharding=NamedSharding(mesh, batch_spec(mesh)))}
    ladder = dict(llama.REMAT_LADDER)
    lowered = {}
    # the library's flash kernel mixes int32 with the default integer
    # and Mosaic refuses a bf16 product at "highest": lowered as the
    # chip's processes run, without conftest's x64 and precision
    with pytest.MonkeyPatch.context() as mp, _as_on_a_tpu(), \
            jax.enable_x64(False), jax.default_matmul_precision("default"):
        for plan in TRAIN_PLANS:
            names = tuple(n for r in plan for n in ladder[r])
            mp.setattr(llama, "remat_plan",
                       lambda *a, names=names: (names, 0))
            step = pstep.make_train_step(llama.loss_fn(cfg, mesh=mesh), tx,
                                         mesh, rules)
            lowered[plan] = step._jitted.lower(state, batch, None)
    return _compile_all(lowered)


def _rematerialised(compiled):
    _, named, _ = tscopes._read(compiled.as_text())
    return sum(1 for op in named.values() if tscopes.scope_path(op)[1])


@pytest.mark.parametrize("less,more", zip(TRAIN_PLANS, TRAIN_PLANS[1:]),
                         ids=["mlp", "attn_out", "the_rest"])
def test_tpu_each_rung_trades_recomputation_for_memory(train_steps, less,
                                                       more):
    """Rung by rung the backward pass holds fewer rematerialised
    instructions and the compiled step more temporaries."""
    a, b = train_steps[less], train_steps[more]
    assert _rematerialised(b) < _rematerialised(a)
    assert b.memory_analysis().temp_size_in_bytes \
        > a.memory_analysis().temp_size_in_bytes
    # (the compiler's peak follows only in the large: at these widths
    # the last rung's 4 MB is inside what buffer assignment moves)
    assert train_steps[TRAIN_PLANS[-1]].memory_analysis() \
        .peak_memory_in_bytes > train_steps[()].memory_analysis() \
        .peak_memory_in_bytes


@pytest.mark.parametrize("plan", TRAIN_PLANS, ids=lambda p: "+".join(p)
                         or "none")
def test_tpu_saved_attention_runs_no_second_forward_kernel(train_steps,
                                                           plan):
    """The step holds the flash forward kernel in the forward loop, and
    dkv and dq in the backward loop. A layer recomputed whole runs the
    forward kernel there again (four Mosaic calls in the text); one that
    kept ``attn_out`` and ``attn_stats`` does not (three): the backward
    rule of ``ops.attention._pallas_flash`` reads what the policy kept."""
    text = train_steps[plan].as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls == (3 if "attn_out" in plan else 4)
    assert text.count("flash_mha_bwd_dkv") and text.count("flash_mha_bwd_dq")
    # the forward kernel under the name a trace shows it by, which the
    # train cell's ``programs.flash_kernels`` finds it by: the library's
    # own jitted entry gave it, ``ops.attention._pallas_flash_fwd``'s
    # scope gives it now. Once in the layers' forward loop, and once
    # more in the backward loop where the layer does not keep its output
    forward = re.findall(r"^\s*%?(flash_attention[\w.\-]*) = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', text, re.M)
    assert len(forward) == (1 if "attn_out" in plan else 2), forward
    assert all(re.match(FLASH_KERNELS, n) for n in forward)
    kernels = re.findall(r'^\s*%?([\w.\-]+) = [^\n]*'
                         r'custom_call_target="tpu_custom_call"', text, re.M)
    assert all(re.match(FLASH_KERNELS, n) for n in kernels), kernels


# ---------------------------------------------------------------------------
# the serve programs the chip runs, against the parent's
# ---------------------------------------------------------------------------
def _program_hash(compiled):
    """A compiled program's text as a hash can hold it: without the
    instructions' metadata and the file tables, and each Mosaic kernel's
    body by its MLIR printed without locations (the bytecode carries the
    Python call sites it was traced through)."""
    from jax._src.lib.mlir import ir

    def kernel(match):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(match.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return '"body":"%s"' % hashlib.sha256(asm.encode()).hexdigest()
    text = re.sub(r", metadata=\{[^}]*\}", "", compiled.as_text())
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', kernel, text)
    text = "\n".join(
        line for line in text.splitlines() if not re.match(
            r"^\s*(\d+ [\"{]|FileNames|FunctionNames|FileLocations"
            r"|StackFrames)", line))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def sambay_prefill(one_chip):
    """The agent cell's family: a 256-token bucket of
    ``prefill_slot_paged``, whose full layer and cross layers run
    ``flash_attention``'s Pallas arm, at the decode fixture's widths."""
    cfg = replace(sambay.CONFIGS["tiny"], vocab_size=8192, dim=512,
                  n_layers=8, n_heads=8, n_kv_heads=4, hidden_dim=512,
                  sliding_window=128, max_seq_len=512, dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16)
    _, params, kv, sv = _lower_decode(sambay, cfg, one_chip)
    arg = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    scalar = partial(arg, ())
    prefill = partial(sambay.prefill_slot_paged, cfg)
    prefill.__name__ = "prefill_slot_paged"
    # as the chip's processes run, without conftest's x64 and precision
    # (``train_steps`` says why)
    with _as_on_a_tpu(), jax.enable_x64(False), \
            jax.default_matmul_precision("default"):
        lowered = jax.jit(prefill, donate_argnums=(6,)).lower(
            params, arg((1, 256), jnp.int32), scalar(jnp.int32),
            scalar(jnp.int32), arg((cfg.max_seq_len // PAGE,), jnp.int32),
            scalar(jnp.int32), kv, sv, arg((2,), jnp.uint32),
            scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.float32))
    return _compile_all({"prefill": lowered})["prefill"]


def test_tpu_block_walk_compiles_with_a_carried_group(one_chip):
    """The sdar cell's attention at its widths (32 slots of 32 query
    heads over 4 KV heads of 128, pools (6, 6401, 16, 512)): the block of
    4 rows a slot and, where a slot carries, the finished block's 4 more,
    in ONE walk of the rows kernel, compiled for a v5e. Mosaic refused
    the first form of the two blocks' body (a static slice of a mask
    vector), which interpret mode ran."""
    from mxtpu.ops.paged_attention import (ROWS_KERNEL_NAME,
                                           paged_attention_block)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = arg((6, 6401, 16, 512), jnp.bfloat16)
    exe = jax.jit(partial(paged_attention_block, scale=0.088)).lower(
        arg((32, 32, 8, 128), jnp.bfloat16), pool, pool,
        arg((32, 200), jnp.int32), arg((32, 2), jnp.int32),
        layer=arg((), jnp.int32)).compile()
    calls = re.findall(r'^\s*%?([\w.\-]+) = [^\n]*'
                       r'custom_call_target="tpu_custom_call"',
                       exe.as_text(), re.M)
    assert len(calls) == 1 and calls[0].startswith(ROWS_KERNEL_NAME)


# of the parent's programs (commit a222295; sambay's decode: 72c1960,
# before the rows walk learned a block's two groups of rows), compiled by
# this file's fixtures in the parent's tree
PARENT_PROGRAMS = {
    "sambay_prefill": "efc129a16255ffb8",
    "prefill_slot_paged": "d9672642aeb4c8ad",
    "decode_slots_paged": "ffc001e06cfef18c",
    "sambay_decode": "18cce40c6e5497e8",
    # of commit 641b7c7, before the expert bank learned a non-gated form
    # and a tile's byte budget: its grouped products, kernel for kernel
    "latent_moe_decode": "c95d4c62f116b14a",
}


@pytest.mark.parametrize("program", list(PARENT_PROGRAMS))
def test_tpu_serve_programs_compile_to_the_parents(request, compiled,
                                                   program):
    """``flash_attention``'s Pallas arm went behind a ``custom_vjp`` of
    this repo's own, with GQA's repeat inside it, and ``_qkv`` /
    ``_out_proj`` / ``_ffn`` name their values for a checkpoint: none of
    it is differentiated or checkpointed in a serve program, so what the
    chip is handed is the parent's program, kernel for kernel. The CPU's
    lowered text (``test_remat_plan.py``) cannot say so: there
    ``flash_attention`` takes the blockwise arm. Sambay's decode step
    holds the rows walk, which learned two blocks of rows a slot
    (``paged_attention_block``): at one length a slot its body is the
    parent's, op for op."""
    exe = request.getfixturevalue(program) \
        if program.startswith(("sambay_", "latent_moe_")) \
        else compiled[program]
    exe = exe[-1] if isinstance(exe, tuple) else exe
    if program == "sambay_prefill":
        forward = re.findall(
            r"^\s*%?(flash_attention[\w.\-]*) = [^\n]*"
            r'custom_call_target="tpu_custom_call"', exe.as_text(), re.M)
        assert forward, "the bucket took another arm than the kernel's"
    assert _program_hash(exe) == PARENT_PROGRAMS[program]
