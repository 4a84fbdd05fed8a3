"""mx.operator — Python custom operators (reference
``python/mxnet/operator.py`` over ``src/operator/custom/custom.cc``
[path cites — unverified]).

The reference ran CustomOp.forward/backward on a dedicated worker thread
pool with GIL handoff; here the host callback is ``jax.pure_callback``,
which makes user numpy code callable from inside jitted programs too —
gradients route through ``jax.custom_vjp`` into the user's
``backward``. The (newer) ``lib_api.h`` C .so path is replaced by the
same mechanism: any ctypes-wrapped native function works inside
forward/backward.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Type

import jax
import jax.numpy as jnp
import numpy as onp

from . import ndarray as nd
from .base import MXNetError, dtype_np
from .ndarray import NDArray
from .ndarray.ndarray import apply_op
from .ndarray.ops import register_op

__all__ = ["CustomOp", "CustomOpProp", "register", "get_all_registered"]

_REGISTRY: Dict[str, Type["CustomOpProp"]] = {}


class CustomOp:
    """User op base (reference ``mx.operator.CustomOp``)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst: NDArray, req: str, src) -> None:
        if req in ("null",):
            return
        src_data = src._data if isinstance(src, NDArray) else \
            jnp.asarray(onp.asarray(src))
        if req == "add":
            dst._set_data(dst._data + src_data.astype(dst.dtype))
        else:                       # 'write' / 'inplace'
            dst._set_data(src_data.astype(dst.dtype).reshape(dst.shape))


class CustomOpProp:
    """Op metadata + factory (reference ``mx.operator.CustomOpProp``)."""

    def __init__(self, need_top_grad: bool = True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), \
            [in_type[0]] * len(self.list_auxiliary_states())

    def infer_storage_type(self, in_stype):
        return in_stype, ["default"] * len(self.list_outputs()), []

    def create_operator(self, ctx, in_shapes, in_dtypes) -> CustomOp:
        raise NotImplementedError


def register(reg_name: str):
    """Register a CustomOpProp subclass under a name (reference
    ``mx.operator.register``); invoke with ``mx.nd.Custom(...,
    op_type=reg_name)``."""
    def deco(prop_cls: Type[CustomOpProp]):
        _REGISTRY[reg_name] = prop_cls
        return prop_cls
    return deco


def get_all_registered() -> List[str]:
    return sorted(_REGISTRY)


def _make_custom(prop: CustomOpProp, n_in: int):
    """Build the custom_vjp'd jax function for one prop instance."""
    out_names = prop.list_outputs()
    n_out = len(out_names)

    def _shapes_dtypes(arrs):
        in_shapes = [list(a.shape) for a in arrs]
        in_dtypes = [onp.dtype(a.dtype) for a in arrs]
        _, out_shapes, _ = prop.infer_shape([list(s) for s in in_shapes])
        _, out_dtypes, _ = prop.infer_type(in_dtypes)
        return ([tuple(s) for s in out_shapes], out_dtypes)

    def _run_forward(is_train, *raw):
        op = prop.create_operator(None, [list(r.shape) for r in raw],
                                  [onp.dtype(r.dtype) for r in raw])
        in_data = [nd.array(onp.asarray(r), dtype=r.dtype) for r in raw]
        out_shapes, out_dtypes = _shapes_dtypes(raw)
        out_data = [nd.zeros(s, dtype=d)
                    for s, d in zip(out_shapes, out_dtypes)]
        op.forward(is_train, ["write"] * n_out, in_data, out_data, [])
        return tuple(o.asnumpy() for o in out_data)

    def _run_backward(*raw):
        # raw = out_grads + in_datas + out_datas
        ogs = raw[:n_out]
        ins = raw[n_out:n_out + n_in]
        outs = raw[n_out + n_in:]
        op = prop.create_operator(None, [list(r.shape) for r in ins],
                                  [onp.dtype(r.dtype) for r in ins])
        in_data = [nd.array(onp.asarray(r), dtype=r.dtype) for r in ins]
        out_data = [nd.array(onp.asarray(r), dtype=r.dtype) for r in outs]
        out_grad = [nd.array(onp.asarray(g), dtype=g.dtype) for g in ogs]
        in_grad = [nd.zeros(i.shape, dtype=i.dtype) for i in in_data]
        op.backward(["write"] * n_in, out_grad, in_data, out_data,
                    in_grad, [])
        return tuple(g.asnumpy() for g in in_grad)

    @jax.custom_vjp
    def fn(*xs):
        out_shapes, out_dtypes = _shapes_dtypes(xs)
        result_shape = tuple(
            jax.ShapeDtypeStruct(s, dtype_np(d))
            for s, d in zip(out_shapes, out_dtypes))
        return jax.pure_callback(
            lambda *r: _run_forward(False, *r), result_shape, *xs)

    def fn_fwd(*xs):
        out_shapes, out_dtypes = _shapes_dtypes(xs)
        result_shape = tuple(
            jax.ShapeDtypeStruct(s, dtype_np(d))
            for s, d in zip(out_shapes, out_dtypes))
        outs = jax.pure_callback(
            lambda *r: _run_forward(True, *r), result_shape, *xs)
        return outs, (xs, outs)

    def fn_bwd(res, gs):
        xs, outs = res
        in_struct = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                          for x in xs)
        grads = jax.pure_callback(_run_backward, in_struct,
                                  *(tuple(gs) + xs + tuple(outs)))
        return tuple(grads)

    fn.defvjp(fn_fwd, fn_bwd)
    return fn


def _eager_custom(prop: CustomOpProp, inputs, op_type: str):
    """Host-side execution with a hand-built tape node: no callback
    from a compiled program is involved, so it needs nothing of the
    backend. This mirrors the reference most closely: CustomOp ran on
    a host worker thread with device↔host copies around it."""
    from . import autograd
    from .ndarray.ndarray import _parents_of

    n_in = len(inputs)
    n_out = len(prop.list_outputs())
    in_shapes = [list(a.shape) for a in inputs]
    in_dtypes = [onp.dtype(a.dtype) for a in inputs]
    _, out_shapes, _ = prop.infer_shape([list(s) for s in in_shapes])
    _, out_dtypes, _ = prop.infer_type(in_dtypes)
    op = prop.create_operator(None, in_shapes, in_dtypes)
    dev = next(iter(inputs[0]._data.devices())) if n_in else None

    in_data = [nd.array(a.asnumpy(), dtype=a.dtype) for a in inputs]
    out_data = [nd.zeros(tuple(s), dtype=d)
                for s, d in zip(out_shapes, out_dtypes)]
    op.forward(autograd.is_training(), ["write"] * n_out, in_data,
               out_data, [])
    out_raw = [jax.device_put(o.asnumpy(), dev) if dev is not None
               else o._data for o in out_data]

    parents = _parents_of(list(inputs))
    node = None
    if autograd.is_recording() and any(p is not None for p in parents):
        def vjp_fn(cot):
            cots = cot if isinstance(cot, tuple) else (cot,)
            out_grad = [nd.array(onp.asarray(c)) for c in cots]
            in_grad = [nd.zeros(i.shape, dtype=i.dtype) for i in in_data]
            op.backward(["write"] * n_in, out_grad, in_data, out_data,
                        in_grad, [])
            return tuple(jax.device_put(g.asnumpy(), dev)
                         if dev is not None else g._data for g in in_grad)

        avals = [(tuple(s), dtype_np(d))
                 for s, d in zip(out_shapes, out_dtypes)]
        node = autograd.Node(vjp_fn, parents, avals,
                             f"Custom[{op_type}]",
                             out_is_tuple=n_out > 1)
    results = []
    for i, o in enumerate(out_raw):
        r = NDArray(o)
        if node is not None:
            r._ag = (node, i)
        results.append(r)
    return results[0] if n_out == 1 else tuple(results)


@register_op("Custom")
def Custom(*inputs, op_type: Optional[str] = None, **kwargs):
    """Run a registered python CustomOp (reference ``mx.nd.Custom``)."""
    if op_type is None or op_type not in _REGISTRY:
        raise MXNetError(f"custom op {op_type!r} is not registered "
                         f"(known: {get_all_registered()})")
    prop = _REGISTRY[op_type](**{k: str(v) for k, v in kwargs.items()})
    tracing = any(isinstance(a._data, jax.core.Tracer) for a in inputs)
    if not tracing:
        return _eager_custom(prop, inputs, op_type)
    # under jit trace (hybridize): lower to pure_callback
    n_out = len(prop.list_outputs())
    raw = _make_custom(prop, len(inputs))
    if n_out == 1:
        return apply_op(lambda *xs: raw(*xs)[0], list(inputs),
                        f"Custom[{op_type}]")
    return apply_op(raw, list(inputs), f"Custom[{op_type}]", n_out=n_out)
