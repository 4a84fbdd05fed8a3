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
"""
from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

__all__ = ["Program", "programs", "register", "scope_map", "scope_path"]

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


def scope_map(hlo_text: str) -> Tuple[str, Dict[str, Tuple[str, bool]]]:
    """Optimised HLO text -> (module name, {instruction name: (scope
    path, rematerialised)}). A fusion that carries no ``op_name`` of
    its own takes its fused computation's root's (or, failing that,
    the one most of its instructions carry)."""
    m = _MODULE.match(hlo_text)
    module = m.group(1) if m else ""
    named: Dict[str, str] = {}
    unnamed_calls: Dict[str, str] = {}
    inside: Dict[str, list] = {}        # computation -> its op_names
    root: Dict[str, str] = {}
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
        if o is not None:
            named[name] = o.group(1)
            if comp is not None:
                inside.setdefault(comp, []).append(o.group(1))
                if line.lstrip().startswith("ROOT"):
                    root[comp] = o.group(1)
        else:
            named[name] = ""
            c = _CALLS.search(line, m.end())
            if c is not None:
                unnamed_calls[name] = c.group(1)
    for name, called in unnamed_calls.items():
        ops = inside.get(called)
        if ops:
            named[name] = root.get(called) or max(set(ops), key=ops.count)
    return module, {name: scope_path(op) for name, op in named.items()}


@dataclass
class Program:
    """One watched program's latest compiled variant: ``name`` is the
    watch name (the ``fn=`` label of ``compile_events_total``),
    ``module`` what a trace calls it (``jit_decode_slots_paged``),
    ``scopes`` the instruction map."""
    name: str
    module: str
    scopes: Dict[str, Tuple[str, bool]] = field(default_factory=dict)
    parse_s: float = 0.0


_lock = threading.Lock()
_programs: Dict[str, Program] = {}


def register(name: str, compiled) -> Program:
    """Catalog ``compiled`` (a jax ``Compiled``) under watch name
    ``name``; a later variant of the same name replaces it."""
    t0 = time.perf_counter()
    module, scopes = scope_map(compiled.as_text())
    prog = Program(name, module, scopes, time.perf_counter() - t0)
    with _lock:
        _programs[name] = prog
    return prog


def programs() -> Dict[str, Program]:
    """The catalog, by watch name (read-only copy)."""
    with _lock:
        return dict(_programs)
