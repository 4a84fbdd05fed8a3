"""Gluon Block / HybridBlock (reference ``python/mxnet/gluon/block.py``
[path cite]).

``hybridize()`` is the reference's trace→CachedOp pipeline
(``src/imperative/cached_op.cc``) rebuilt on jax: the FIRST hybrid call
runs eagerly (resolving deferred shapes, exactly like CachedOp's first-call
shape passes); afterwards the whole net is ONE jitted function

    raw(inputs..., params..., rng_key) -> ((outputs...), (aux_updates...))

whose forward is a single XLA program and whose backward (via the autograd
tape's ``jax.vjp`` over it) is another — MXNet's "one optimized unit, static
memory planning" becomes XLA buffer assignment + fusion. Aux updates carry
mutated non-differentiable state (BatchNorm running stats) out of the pure
function, mirroring the reference's mutable aux_states.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .. import autograd
from .. import ndarray as nd
from ..base import MXNetError
from ..ndarray import NDArray
from ..ndarray import random as _random
from .parameter import (DeferredInitializationError, Parameter, ParameterDict)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


# ---------------------------------------------------------------------------
# naming
# ---------------------------------------------------------------------------
class _NameManager:
    _current = threading.local()

    def __init__(self):
        self._counter: Dict[str, int] = {}

    @classmethod
    def get(cls) -> "_NameManager":
        if not hasattr(cls._current, "value"):
            cls._current.value = _NameManager()
        return cls._current.value

    def next_prefix(self, hint: str) -> str:
        count = self._counter.get(hint, 0)
        self._counter[hint] = count + 1
        return f"{hint}{count}_"


class _BlockScope:
    """Name scope: children created inside ``with block.name_scope():``
    get prefixes nested under the block's prefix (reference behavior)."""

    _current = threading.local()

    def __init__(self, block: "Block"):
        self._block = block
        self._counter: Dict[str, int] = {}
        self._old_scope = None

    @staticmethod
    def create(prefix: Optional[str], params: Optional[ParameterDict],
               hint: str) -> Tuple[str, ParameterDict]:
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _NameManager.get().next_prefix(hint)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, shared=params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix)
        else:
            params = ParameterDict(params.prefix, shared=params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


# ---------------------------------------------------------------------------
# NDArray pytree helpers (NDArray is deliberately NOT a jax pytree — flatten
# explicitly at the hybridize boundary)
# ---------------------------------------------------------------------------
def _flatten_nds(obj, out: List[NDArray]):
    if isinstance(obj, NDArray):
        out.append(obj)
        return ("_",)
    if isinstance(obj, (list, tuple)):
        return tuple(_flatten_nds(x, out) for x in obj)
    out.append(obj)  # non-array leaf passes through untouched
    return ("_",)


def _unflatten_nds(tree, flat: List[Any], pos: List[int]):
    if tree == ("_",):
        val = flat[pos[0]]
        pos[0] += 1
        return val
    return tuple(_unflatten_nds(t, flat, pos) for t in tree)


_TRACE_DEPTH = threading.local()
_SYM_MODE = threading.local()


def _in_trace() -> bool:
    return getattr(_TRACE_DEPTH, "depth", 0) > 0


def _in_symbolic() -> bool:
    return getattr(_SYM_MODE, "active", False)


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------
class Block:
    """Base class for all layers/models (imperative, reference
    ``gluon.Block``)."""

    def __init__(self, prefix: Optional[str] = None,
                 params: Optional[ParameterDict] = None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}

    def _alias(self) -> str:
        return self.__class__.__name__.lower()

    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self) -> _BlockScope:
        return self._scope

    def __repr__(self):
        s = f"{self.__class__.__name__}("
        for k, v in self._children.items():
            s += f"\n  ({k}): " + repr(v).replace("\n", "\n  ")
        return s + ("\n)" if self._children else ")")

    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Block):
            existing = self._children.get(name) \
                if hasattr(self, "_children") else None
            if existing is not None:
                self._children[name] = value
            else:
                self.register_child(value, name)
        elif isinstance(value, Parameter):
            if not hasattr(self, "_reg_params"):
                raise RuntimeError(
                    "call Block.__init__ before assigning Parameters")
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None) -> None:
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        """All parameters of this block and children, optionally filtered
        by regex (reference semantics: ``select`` matches anywhere)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            ret._params.update(
                {k: v for k, v in self._params.items() if pat.match(k)})
        for p in self._reg_params.values():
            if select is None or re.compile(select).match(p.name):
                if p.name not in ret._params:
                    ret._params[p.name] = p
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def _collect_params_with_prefix(self, prefix: str = "") -> Dict[str, Parameter]:
        """Attribute-path parameter names ('features.0.weight') used by
        save_parameters/load_parameters (reference behavior — portable
        across prefix differences)."""
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def initialize(self, init=None, ctx=None, verbose: bool = False,
                   force_reinit: bool = False) -> None:
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active: bool = True, **kwargs) -> None:
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype) -> None:
        for child in self._children.values():
            child.cast(dtype)
        for p in self.collect_params().values():
            p.cast(dtype)

    def shard(self, mesh, rules) -> "Block":
        """Place every Parameter onto ``mesh`` per the ShardingRules
        table, keyed on parameter NAMES (VERDICT r2 #1 — the Gluon
        surface's entry to dp/fsdp/tp/sp parallelism; the reference
        reached multi-device through per-GPU copies + KVStore instead).

        After ``shard``, a hybridized forward is one GSPMD-partitioned
        program (XLA inserts the collectives), and
        ``Trainer.make_fused_step(net)`` lowers the whole train step
        to one donated program. Re-sharding with a different mesh or
        rules is allowed and clears compiled caches (this block and
        all descendants). Gradient buffers are re-created ZEROED on
        the parameter's sharding — shard() is a placement change, not
        a step boundary; don't call it mid-accumulation."""
        from jax.sharding import NamedSharding
        from ..parallel.sharding import global_device_put
        for p in self.collect_params().values():
            if p._data is None:
                if p._deferred_init:
                    raise MXNetError(
                        f"parameter {p.name} has a deferred shape; run "
                        "one forward before shard() so shapes resolve")
                raise MXNetError(
                    f"parameter {p.name} is uninitialized; call "
                    "initialize() before shard()")
            sharding = NamedSharding(mesh, rules.spec(p.name))
            grad_req = p._grad_req
            p._data._set_data(global_device_put(p._data._data, sharding))
            if grad_req != "null":       # grads live on the same layout
                p._data.attach_grad(grad_req)
                p._data.grad._set_data(
                    global_device_put(p._data.grad._data, sharding))
            p._sharding = sharding

        def mark(b):
            b._mesh, b._shard_rules = mesh, rules
            if hasattr(b, "_clear_cached_op"):
                b._clear_cached_op()
            for c in b._children.values():
                mark(c)
        mark(self)
        return self

    def apply(self, fn: Callable[["Block"], None]) -> "Block":
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- serialization ------------------------------------------------------
    def save_parameters(self, filename: str) -> None:
        params = self._collect_params_with_prefix()
        nd.save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename: str, ctx=None,
                        allow_missing: bool = False,
                        ignore_extra: bool = False,
                        cast_dtype: bool = False) -> None:
        from ..model import split_arg_aux
        arg_p, aux_p = split_arg_aux(nd.load(filename))
        loaded = {**arg_p, **aux_p}
        params = self._collect_params_with_prefix()
        if not allow_missing:
            missing = [k for k in params if k not in loaded]
            if missing:
                raise RuntimeError(
                    f"parameters {missing} missing in file {filename}")
        if not ignore_extra:
            extra = [k for k in loaded if k not in params]
            if extra:
                raise RuntimeError(
                    f"file {filename} contains extra parameters {extra}")
        for k, v in loaded.items():
            if k in params:
                if cast_dtype:
                    v = v.astype(params[k].dtype)
                params[k]._load_init(v, ctx)

    save_params = save_parameters
    load_params = load_parameters

    # -- execution ----------------------------------------------------------
    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------
class HybridBlock(Block):
    """Block that can be compiled to one XLA program via ``hybridize()``."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op_params: Optional[List[Parameter]] = None
        self._raw_cache: Dict[Any, Callable] = {}
        self._aux_params_for: Dict[Any, List[Parameter]] = {}
        self._out_tree_for: Dict[Any, Any] = {}

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, mesh=None, rules=None,
                  **kwargs) -> None:
        """``hybridize(mesh=..., rules=...)`` additionally shards the
        net (sugar for ``hybridize(); shard(mesh, rules)``)."""
        self._active = active
        self._clear_cached_op()
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)
        if mesh is not None:
            if rules is None:
                from ..parallel.sharding import ShardingRules
                rules = ShardingRules([])
            self.shard(mesh, rules)

    def _clear_cached_op(self) -> None:
        self._cached_op_params = None
        self._raw_cache = {}
        self._aux_params_for = {}
        self._out_tree_for = {}

    def cast(self, dtype) -> None:
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args) -> None:
        """Resolve deferred parameter shapes from input shapes. Layers with
        deferred-init parameters override this (the reference resolves it
        generically through symbolic infer_shape passes)."""
        raise MXNetError(
            f"{self.__class__.__name__} has parameters with deferred "
            "(unknown) shapes but does not implement infer_shape(); "
            "specify in_units/in_channels explicitly")

    # -- eager path ---------------------------------------------------------
    def forward(self, x, *args):
        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self.infer_shape(x, *args)
            for p in self._reg_params.values():
                p._finish_deferred_init()
            params = {k: p.data() for k, p in self._reg_params.items()}
        return self.hybrid_forward(nd, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- cached (jitted) path -----------------------------------------------
    def __call__(self, *args):
        if _in_symbolic():
            return self._symbolic_call(*args)
        if self._active and not _in_trace():
            return self._call_cached_op(*args)
        return super().__call__(*args)

    def _call_cached_op(self, *args):
        if self._cached_op_params is None:
            params = list(self.collect_params().values())
            if any(p._data is None for p in params):
                # first call: run eagerly to resolve deferred shapes (the
                # reference's first-call shape/type/storage passes)
                out = super().__call__(*args)
                return out
            self._cached_op_params = params
        params = self._cached_op_params
        flat_in: List[Any] = []
        in_tree = _flatten_nds(args, flat_in)
        training = autograd.is_training()
        cache_key = (training, in_tree)
        raw = self._raw_cache.get(cache_key)
        if raw is None:
            raw = self._build_raw(training, in_tree, len(flat_in), cache_key)
            self._raw_cache[cache_key] = raw
        datas = [a._data if isinstance(a, NDArray) else a for a in flat_in]
        mesh = getattr(self, "_mesh", None)
        if mesh is not None:
            # sharded net: inputs must live on the same mesh as the
            # params. Inputs the caller already placed on THIS mesh
            # (e.g. a dp-sharded inference batch) pass through
            # untouched; everything else replicates. The fused train
            # step dp-shards its own batch.
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel.sharding import global_device_put

            def place(d):
                if not isinstance(d, jax.Array):
                    return d
                s = d.sharding
                if isinstance(s, NamedSharding) and s.mesh == mesh:
                    return d
                # global_device_put, not jax.device_put: on a
                # multi-process global mesh a committed device-backed
                # input would make plain device_put raise (the mesh is
                # not fully addressable from this host).
                return global_device_put(
                    d, NamedSharding(mesh, PartitionSpec()))
            datas = [place(d) for d in datas]
        datas += [p.data()._data for p in params]
        datas.append(_random._next_key())

        from ..ndarray.ndarray import _parents_of
        parent_arrays = list(flat_in) + [p.data() for p in params] + [None]
        parents = _parents_of(
            [a if isinstance(a, NDArray) else None for a in parent_arrays])
        import contextlib
        if mesh is not None:           # sharded net: trace/run with the
            from ..parallel.mesh import use_mesh   # ambient mesh so
            cm = use_mesh(mesh)        # constrain() in model code binds
        else:
            cm = contextlib.nullcontext()
        with cm:
            result, node = autograd.invoke(
                raw, datas, parents, f"CachedOp[{self.name}]",
                has_aux=True)
        outs, aux = result
        # write mutated aux state back into the real parameters
        aux_params = self._aux_params_for[cache_key]
        with autograd.pause():
            for p, v in zip(aux_params, aux):
                p.set_data(v)
        out_nds = []
        for i, o in enumerate(outs):
            r = NDArray(o)
            if node is not None:
                r._ag = (node, i)
            out_nds.append(r)
        res = _unflatten_nds(self._out_tree_for[cache_key], out_nds, [0])
        return res[0] if len(res) == 1 else res

    def _build_raw(self, training: bool, in_tree, n_in: int, cache_key):
        params = self._cached_op_params
        block = self

        def raw(*datas):
            xs = list(datas[:n_in])
            ps = datas[n_in:n_in + len(params)]
            key = datas[-1]
            for p, d in zip(params, ps):
                p._bind_tracer(d)
            _random.push_trace_key(key)
            _TRACE_DEPTH.depth = getattr(_TRACE_DEPTH, "depth", 0) + 1
            try:
                with autograd.pause(train_mode=training):
                    # wrap only traced array values; pass-through leaves
                    # (None, python scalars) stay as-is
                    wrapped = [NDArray(x) if isinstance(
                        x, (jax.Array, jax.core.Tracer)) else x for x in xs]
                    args = _unflatten_nds(in_tree, wrapped, [0])
                    out = block.forward(*args)
            finally:
                _TRACE_DEPTH.depth -= 1
                _random.pop_trace_key()
                new_vals = [p._unbind_tracer() for p in params]
            aux_params, aux_vals = [], []
            for p, d, nv in zip(params, ps, new_vals):
                if nv is not d:
                    aux_params.append(p)
                    aux_vals.append(nv)
            block._aux_params_for[cache_key] = aux_params
            flat_out: List[Any] = []
            out_tree = _flatten_nds((out,) if isinstance(out, NDArray)
                                    else out, flat_out)
            block._out_tree_for[cache_key] = out_tree
            return (tuple(o._data if isinstance(o, NDArray) else o
                          for o in flat_out), tuple(aux_vals))

        jitted = jax.jit(raw)
        # stable across steps → autograd caches one jitted backward
        jitted._mx_cache_vjp = True
        return jitted

    # -- symbolic tracing / deploy ------------------------------------------
    def _symbolic_call(self, *args):
        """Trace this block with Symbol inputs → Symbol outputs (the
        reference's _build_cache trace of hybrid_forward with Symbol
        placeholders, python/mxnet/gluon/block.py)."""
        import mxtpu.symbol as sym
        # non-differentiable state (grad_req='null') must export as an aux
        # var regardless of its name, so SymbolBlock.imports reconstructs
        # it as frozen state
        param_syms = {k: sym.var(p.name, aux=p.grad_req == "null")
                      for k, p in self._reg_params.items()}
        return self.hybrid_forward(sym, *args, **param_syms)

    def _trace_symbol(self, *input_syms):
        """Run the whole net symbolically. Any initialized HybridBlock
        works — children are traced through __call__ via the thread-local
        symbolic mode."""
        prev = getattr(_SYM_MODE, "active", False)
        _SYM_MODE.active = True
        try:
            out = self(*input_syms)
        finally:
            _SYM_MODE.active = prev
        return out

    def export(self, path: str, epoch: int = 0, num_inputs: int = 1) -> None:
        """Save the traced graph + params in the reference's export layout
        (``prefix-symbol.json`` + ``prefix-%04d.params``, reference
        HybridBlock.export) so SymbolBlock.imports / the C predict path
        can reload it without the Python class. Multi-input nets pass
        ``num_inputs`` (vars are named data0, data1, ...)."""
        import mxtpu.symbol as sym
        n_in = num_inputs
        inputs = [sym.var("data" if n_in == 1 else f"data{i}")
                  for i in range(n_in)]
        out = self._trace_symbol(*inputs)
        if isinstance(out, (list, tuple)):
            out = sym.Group(list(out))
        out.save(f"{path}-symbol.json")
        aux_names = set(out.list_auxiliary_states())
        params = {}
        for p in self.collect_params().values():
            kind = "aux:" if p.name in aux_names else "arg:"
            params[kind + p.name] = p.data()
        nd.save(f"{path}-{epoch:04d}.params", params)

    def export_stablehlo(self, path: str, *example_inputs):
        """Serialize the inference forward as a portable StableHLO
        artifact (weights baked in) — the TPU-native analogue of the
        reference's ``net.export`` → C predict deploy path (SURVEY
        §7.0: "net.export = StableHLO/orbax-export"). Reload anywhere
        with ``mxtpu.contrib.deploy.load`` (no Python class needed) and
        run on any jax backend. Shapes are fixed to the example
        inputs'."""
        from .. import autograd as _ag
        ex = [x if isinstance(x, NDArray) else nd.array(x)
              for x in example_inputs]
        with _ag.pause(train_mode=False):
            self(*ex)          # resolves deferred shapes if any
        params = list(self.collect_params().values())
        pvals = [p.data()._data for p in params]

        def infer(*xs):
            for p, v in zip(params, pvals):
                p._bind_tracer(v)
            try:
                with _ag.pause(train_mode=False):
                    out = self(*[NDArray(x) for x in xs])
            finally:
                for p in params:
                    p._unbind_tracer()
            outs = out if isinstance(out, (list, tuple)) else (out,)
            return tuple(o._data for o in outs)

        exp = jax.export.export(jax.jit(infer))(*[x._data for x in ex])
        out_path = path if path.endswith(".stablehlo") else \
            path + ".stablehlo"
        with open(out_path, "wb") as f:
            f.write(exp.serialize())
        return out_path


# ---------------------------------------------------------------------------
# SymbolBlock
# ---------------------------------------------------------------------------
class SymbolBlock(HybridBlock):
    """Run a Symbol graph as a Gluon block (reference ``gluon.SymbolBlock``)
    — the reload path for ``HybridBlock.export`` artifacts.

    Parameters are created from the symbol's argument/aux lists (minus the
    declared inputs); shapes resolve from the params file or lazily from
    the first forward's input shapes via abstract evaluation.
    """

    def __init__(self, outputs, inputs, params=None, prefix=None):
        super().__init__(prefix=prefix or "", params=None)
        import mxtpu.symbol as sym
        if isinstance(outputs, (list, tuple)):
            outputs = sym.Group(list(outputs))
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._sb_symbol = outputs
        self._input_names = [i.name if isinstance(i, sym.Symbol) else str(i)
                             for i in inputs]
        aux_names = set(outputs.list_auxiliary_states())
        self._sb_params: Dict[str, Parameter] = {}
        loaded = params or {}
        for name in outputs.list_inputs():
            if name in self._input_names:
                continue
            p = Parameter(name,
                          grad_req="null" if name in aux_names else "write",
                          shape=None, allow_deferred_init=True,
                          differentiable=name not in aux_names)
            if name in loaded:
                p._load_init(loaded[name], None)
            self._sb_params[name] = p
            self._reg_params[name] = p

    @classmethod
    def imports(cls, symbol_file: str, input_names, param_file=None,
                ctx=None) -> "SymbolBlock":
        """Load an exported prefix-symbol.json (+ params) — reference
        ``SymbolBlock.imports``."""
        import mxtpu.symbol as sym
        out = sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        params = {}
        if param_file:
            from ..model import split_arg_aux
            arg_p, aux_p = split_arg_aux(nd.load(param_file))
            params = {**arg_p, **aux_p}
        inputs = [sym.var(n) for n in input_names]
        block = cls(out, inputs, params=params)
        if ctx is not None:
            block.collect_params().reset_ctx(ctx) \
                if hasattr(block.collect_params(), "reset_ctx") else None
        return block

    def _resolve_shapes(self, *args) -> None:
        import jax as _jax
        shapes = {n: _jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for n, a in zip(self._input_names, args)
                  if isinstance(a, NDArray)}
        for n, p in self._sb_params.items():
            if p.shape is not None and 0 not in p.shape:
                shapes[n] = _jax.ShapeDtypeStruct(p.shape, p.dtype)
        structs = self._sb_symbol._infer_structs(**shapes)
        if structs is None:
            raise MXNetError("SymbolBlock: cannot infer parameter shapes "
                             "from input shapes")
        _, var_structs = structs
        for n, p in self._sb_params.items():
            if p.shape is None or 0 in (p.shape or (0,)):
                p.shape = tuple(var_structs[n].shape)

    def forward(self, *args):
        from mxtpu.symbol.symbol import interpret_nd
        unresolved = [p for p in self._sb_params.values()
                      if p.shape is None or (p.shape and 0 in p.shape)]
        if unresolved and any(p._data is None for p in unresolved):
            self._resolve_shapes(*args)
            for p in self._sb_params.values():
                if p._data is None and p._deferred_init:
                    p._finish_deferred_init()
        values = dict(zip(self._input_names, args))
        for n, p in self._sb_params.items():
            values[n] = p.data()
        outs, aux_up = interpret_nd(self._sb_symbol._entries, values)
        if aux_up:
            with autograd.pause():
                for n, v in aux_up.items():
                    self._sb_params[n].set_data(v)
        return outs[0] if len(outs) == 1 else tuple(outs)

    def _symbolic_call(self, *args):
        # re-exporting a SymbolBlock: splice the stored graph
        import mxtpu.symbol as sym
        mapping = dict(zip(self._input_names, args))
        return _splice_symbol(self._sb_symbol, mapping)


def _splice_symbol(symbol, input_map):
    """Rebuild a symbol graph substituting input vars (for re-export)."""
    import mxtpu.symbol as sym
    from mxtpu.symbol.symbol import _Node, Symbol
    memo = {}

    def clone(node):
        if id(node) in memo:
            return memo[id(node)]
        if node.op == "null" and node.name in input_map:
            repl = input_map[node.name]._entries[0][0]
            memo[id(node)] = repl
            return repl
        new = _Node(node.op, node.name, dict(node.attrs),
                    [(clone(p), i) for p, i in node.inputs])
        memo[id(node)] = new
        return new

    entries = [(clone(n), i) for n, i in symbol._entries]
    return Symbol(entries)

