"""Compiled-program catalog: what each watched program is called in a
device trace, and which ``jax.named_scope`` each of its instructions
came from.

A device trace names an operation by its HLO instruction
(``fusion.13``) inside a module (``jit_decode_slots_paged``); the v5e
trace carries no framework scope, and fusion numbers change with every
compile. The optimised HLO text of the executable does carry the
scope: every instruction's ``metadata={op_name="jit(decode_slots_paged)
/vmap(sampler)/sort"}`` is the name stack it was
traced under. :func:`scope_map` reads that text once per compiled
variant (``WatchedFunction._on_compile`` -> ``perfscope.profile_program``
-> :func:`register`, the executable the cost catalog already holds) and
keeps ``{instruction: (scope path, rematerialised)}``;
:func:`programs` publishes it with the watch name and the module name,
so a reader of the trace can follow ``sampler`` from one compile to the
next. Parsing happens at compile only.

The same catalog holds how each program was BUILT and what the build
produced. The build's seconds (trace, lowering, backend) and the
compile cache's answer come from ``telemetry/watcher.py``'s listener,
which adds them to the record :func:`building` hands it while the
program's first call runs; :func:`register` then takes that record and,
in the one pass over the text it makes anyway, counts the Mosaic calls
and what XLA placed in fast memory (``S(1)`` in a result's layout).
Builds outside every watched call share one row, ``others``.
"""
from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

__all__ = ["Program", "programs", "register", "building", "built",
           "scope_map", "scope_path", "OTHERS"]

# the row of every build outside a watched call (``init_params``, a
# check's loss, casts, seeds)
OTHERS = "others"

# what jax's own machinery puts on the name stack: higher-order
# primitives and their sub-jaxprs. Anything else that is not the
# instruction's own primitive is a named scope.
_STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "rematted_computation", "remat", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin",
    "shard_map", "pallas_call", "xla_call", "pjit"))
_BRANCH = re.compile(r"branch_\d+_fun$")
# a transform around a scope, ``vmap(sampler)``, ``transpose(jvp(mlp))``,
# or around nothing, ``jvp()``; ``jit(name)`` is a function, not a scope
_WRAPPED = re.compile(r"(\w+)\((.*)\)$")
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
_MODULE = re.compile(r"HloModule ([\w.\-]+)")
REMAT = "rematted_computation"
# an array in a result type that XLA placed in fast memory (VMEM on a
# TPU): ``bf16[8,4096]{1,0:T(8,128)(2,1)S(1)}``
_FAST = re.compile(r"(\w+)\[([\d,]*)\]\{[^}]*S\(1\)[^}]*\}")
_OPCODE = re.compile(r" ([\w\-]+)\(")
# these name a buffer another instruction made
_ALIASES = frozenset(("parameter", "get-tuple-element", "tuple", "bitcast"))
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_BITS = re.compile(r"\d+")


def _split(op_name: str):
    """``a/vmap(jit(f)/b)/c`` -> its top-level components."""
    depth, start = 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            yield op_name[start:i]
            start = i + 1
    yield op_name[start:]


def _scopes(component: str):
    """The named scopes one component of a name stack holds."""
    m = _WRAPPED.match(component)
    if m is None:
        if component not in _STRUCTURAL and not _BRANCH.match(component):
            yield component
    elif m.group(1) not in ("jit", "pjit"):
        for inner in _split(m.group(2)):
            if inner:
                yield from _scopes(inner)


def scope_path(op_name: str) -> Tuple[str, bool]:
    """An instruction's ``op_name`` -> (scope path, rematerialised).
    The last component is the instruction's own primitive and is taken
    off; ``jit(..)``, the loop and call machinery and the transforms'
    wrappers go too, and what is left are the ``jax.named_scope`` names
    from the outside in, joined by ``/`` (empty: under no scope).
    Rematerialised = traced under ``rematted_computation``, the
    backward pass's second run of a checkpointed forward (the forward
    pass itself runs under ``checkpoint`` alone)."""
    if ";" in op_name:
        # XLA merged several instructions' names: their common prefix,
        # then each one's own tail; the most deeply scoped one stands
        # for the merged instruction
        first, *tails = op_name.split(";")
        prefix = first[:first.rfind("/") + 1]
        return max((scope_path(n) for n in
                    [first] + [prefix + t for t in tails]),
                   key=lambda pr: pr[0].count("/") + bool(pr[0]))
    parts = list(_split(op_name))[:-1]
    path = [s for part in parts for s in _scopes(part)]
    return "/".join(path), REMAT in op_name


def _result_type(line: str, start: int) -> str:
    """The result type of the instruction whose ``name = `` ends at
    ``start``: one array, or a tuple in parentheses (a layout holds
    parentheses and no space)."""
    if line[start] != "(":
        return line[start:line.find(" ", start)]
    depth = 0
    for i in range(start, len(line)):
        depth += (line[i] == "(") - (line[i] == ")")
        if depth == 0:
            return line[start:i + 1]
    return line[start:]


def _fast_bytes(result_type: str) -> Tuple[int, int]:
    """(arrays, bytes) of a result type that lie in fast memory, bytes
    by shape and element type (the tiling's padding is not counted)."""
    n = nbytes = 0
    for dtype, dims in _FAST.findall(result_type):
        bits = _BITS.search(dtype)
        size = 1
        for d in dims.split(","):
            size *= int(d) if d else 1
        # ``pred`` has no width in its name and takes a byte
        nbytes += -(-size * (int(bits.group()) if bits else 8) // 8)
        n += 1
    return n, nbytes


def _read(hlo_text: str):
    """One pass over optimised HLO text -> (module name, {instruction:
    op_name}, what was built: ``custom_calls``, ``fast_mem_buffers``,
    ``fast_mem_bytes``). The fast-memory tally counts the instructions
    outside fused computations that make a buffer (a fusion's inside
    names its operands and its root again)."""
    m = _MODULE.match(hlo_text)
    module = m.group(1) if m else ""
    named: Dict[str, str] = {}
    unnamed_calls: Dict[str, str] = {}
    inside: Dict[str, list] = {}        # computation -> its op_names
    root: Dict[str, str] = {}
    fast: Dict[Optional[str], list] = {}    # computation -> [n, bytes]
    fused = set()
    mosaic = 0
    comp: Optional[str] = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        o = _OP_NAME.search(line, m.end())
        c = _CALLS.search(line, m.end())
        if o is not None:
            named[name] = o.group(1)
            if comp is not None:
                inside.setdefault(comp, []).append(o.group(1))
                if line.lstrip().startswith("ROOT"):
                    root[comp] = o.group(1)
        else:
            named[name] = ""
            if c is not None:
                unnamed_calls[name] = c.group(1)
        if c is not None and " fusion(" in line:
            fused.add(c.group(1))
        if _MOSAIC in line:
            mosaic += 1
        if "S(1)" in line:
            rtype = _result_type(line, m.end())
            op = _OPCODE.match(line, m.end() + len(rtype))
            if op is None or op.group(1) not in _ALIASES:
                n, nbytes = _fast_bytes(rtype)
                tally = fast.setdefault(comp, [0, 0])
                tally[0] += n
                tally[1] += nbytes
    for name, called in unnamed_calls.items():
        ops = inside.get(called)
        if ops:
            named[name] = root.get(called) or max(set(ops), key=ops.count)
    kept = [t for c, t in fast.items() if c not in fused]
    return module, named, {
        "custom_calls": mosaic,
        "fast_mem_buffers": sum(t[0] for t in kept),
        "fast_mem_bytes": sum(t[1] for t in kept)}


def scope_map(hlo_text: str) -> Tuple[str, Dict[str, Tuple[str, bool]]]:
    """Optimised HLO text -> (module name, {instruction name: (scope
    path, rematerialised)}). A fusion that carries no ``op_name`` of
    its own takes its fused computation's root's (or, failing that,
    the one most of its instructions carry)."""
    module, named, _ = _read(hlo_text)
    return module, {name: scope_path(op) for name, op in named.items()}


@dataclass
class Program:
    """One watched program's latest compiled variant: ``name`` is the
    watch name (the ``fn=`` label of ``compile_events_total``),
    ``module`` what a trace calls it (``jit_decode_slots_paged``),
    ``scopes`` the instruction map.

    How it was built, from jax's own events inside the call that built
    it: ``trace_s`` the program's own trace (inclusive), ``nested_traces``
    / ``nested_trace_s`` every other trace inside that call (a ``jnp``
    function traced under the program's; they lie inside ``trace_s`` or
    ``lower_s`` and are not added to either), ``lower_s`` lowering to
    MLIR (a kernel's Mosaic lowering included), ``backend_s`` compile or
    fetch, ``compiled`` / ``fetched`` how many executables the backend
    compiled and how many the persistent cache held, ``cache`` the
    cache's answer for the program itself (``hit``; ``miss``: compiled
    and written; ``unwritten``: compiled under jax's thresholds and not
    written, so compiled again in every process; ``off``),
    ``first_call_s`` the wall time of the building call.

    What was built: ``custom_calls`` Mosaic calls in the program,
    ``fast_mem_buffers`` / ``fast_mem_bytes`` what XLA placed in fast
    memory, ``temp_bytes`` from the executable's ``memory_analysis()``
    (``perfscope.catalog()`` has the rest of it). ``parse_s`` is what
    reading the text cost. ``remat_plan`` / ``remat_saved_bytes``: the
    names a train step's checkpointed layers keep and their bytes a
    device (``None``: the build made no plan)."""
    name: str
    module: str = ""
    scopes: Dict[str, Tuple[str, bool]] = field(default_factory=dict)
    parse_s: float = 0.0
    trace_s: float = 0.0
    nested_traces: int = 0
    nested_trace_s: float = 0.0
    lower_s: float = 0.0
    backend_s: float = 0.0
    compiled: int = 0
    fetched: int = 0
    cache: str = ""
    first_call_s: float = 0.0
    custom_calls: int = 0
    fast_mem_buffers: int = 0
    fast_mem_bytes: int = 0
    temp_bytes: Optional[int] = None
    remat_plan: Optional[Tuple[str, ...]] = None
    remat_saved_bytes: int = 0


_lock = threading.Lock()
_programs: Dict[str, Program] = {}
_building: Dict[str, Program] = {}


def building(name: str) -> Program:
    """The record of the build in progress under ``name``, for the
    compile listener to add to. :func:`built` ends it; ``others``
    never ends."""
    with _lock:
        prog = _building.get(name)
        if prog is None:
            prog = _building[name] = Program(name)
        return prog


# how a program was built: the listener's fields
_BUILD_SUMS = ("trace_s", "nested_traces", "nested_trace_s", "lower_s",
               "backend_s", "compiled", "fetched", "first_call_s")


def register(name: str, compiled,
             temp_bytes: Optional[float] = None) -> Program:
    """Catalog ``compiled`` (a jax ``Compiled``) under watch name
    ``name``: what was built. A later variant of the same name replaces
    it; :func:`built` then says how it was built."""
    t0 = time.perf_counter()
    module, named, made = _read(compiled.as_text())
    scopes = {n: scope_path(op) for n, op in named.items()}
    with _lock:
        old = _programs.get(name)
    prog = replace(
        old or Program(name), module=module, scopes=scopes,
        temp_bytes=None if temp_bytes is None else int(temp_bytes), **made)
    prog.parse_s = time.perf_counter() - t0
    with _lock:
        _programs[name] = prog
    return prog


def built(name: str, first_call_s: float) -> None:
    """The call that built ``name`` is over: its record goes to the
    catalog's entry (one without a module or a map where nobody
    catalogued the executable: perfscope off). A call that grew the jit
    cache and never asked the backend (the same executable under
    another signature of its arguments) built nothing: its seconds are
    added to the record of the build that did."""
    with _lock:
        new = _building.pop(name, None) or Program(name)
        prog = _programs.setdefault(name, Program(name))
    new.first_call_s = first_call_s
    fresh = bool(new.compiled or new.fetched)
    for f in _BUILD_SUMS:
        setattr(prog, f, getattr(new, f) + (0 if fresh else getattr(prog, f)))
    if fresh:
        prog.cache = new.cache
    if new.remat_plan is not None:
        prog.remat_plan = new.remat_plan
        prog.remat_saved_bytes = new.remat_saved_bytes


def programs() -> Dict[str, Program]:
    """The catalog, by watch name (read-only copy), and ``others`` once
    anything was built outside a watched call."""
    with _lock:
        out = dict(_programs)
        if OTHERS in _building:
            out[OTHERS] = _building[OTHERS]
        return out
