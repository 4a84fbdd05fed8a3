"""NDArray: MXNet's imperative mutable array over an immutable ``jax.Array``.

Rebuild of the reference NDArray (``src/ndarray/ndarray.cc``,
``include/mxnet/ndarray.h``, ``python/mxnet/ndarray/ndarray.py`` [path
cite]). The reference pairs each array with an engine variable and pushes
every op to the ThreadedEngine; here the asynchrony comes for free from
XLA/PJRT async dispatch (a ``jax.Array`` is a future), so:

- ``WaitToRead``  → ``jax.Array.block_until_ready`` (it does fence:
  ``chip_smoke.py`` times it against a read-back on every run)
- engine var + version → a Python-level ``_version`` counter; "mutation"
  rebinds ``_data`` to a new jax.Array (buffer donation inside jitted
  update steps recovers in-place performance where it matters)
- FCompute dispatch → plain jnp/lax calls, traced by jax per-op (cached)
- autograd entry (AGInfo) → ``_ag`` tape link (see mxtpu/autograd.py)

`MXNET_ENGINE_TYPE=NaiveEngine` forces a block after every op — the
reference's synchronous-debugging engine (src/engine/naive_engine.cc).
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as _np

from .. import autograd
from ..base import MXNetError, dtype_np, env_str, numeric_types
from ..context import Context, current_context

__all__ = ["NDArray", "array", "empty", "zeros", "ones", "full", "arange",
           "concat", "stack", "waitall", "from_jax", "save", "load"]

_NAIVE = env_str("MXNET_ENGINE_TYPE", "ThreadedEngine") == "NaiveEngine"


# installed by mxtpu.profiler when profiling: fn(op_name, dispatch_secs)
_profile_hook = None
from time import perf_counter as _perf_counter  # noqa: E402


def _parents_of(arrays) -> List[Any]:
    """Tape parent descriptor for each NDArray input (None for constants)."""
    out = []
    for a in arrays:
        if isinstance(a, NDArray):
            if a._ag is not None:
                out.append(a._ag)
            elif a._ag_leaf is not None:
                out.append(a._ag_leaf)
            else:
                out.append(None)
        else:
            out.append(None)
    return out


def apply_op(raw_fn: Callable, arrays: Sequence["NDArray"], name: str = "",
             n_out: int = 1):
    """Execute an op on NDArrays through the autograd-aware path.

    ``raw_fn`` takes/returns jax arrays (tuple when n_out > 1). This is the
    single funnel every imperative op goes through — the analogue of
    Imperative::Invoke → Engine::PushAsync (src/imperative/imperative.cc).
    """
    parents = _parents_of(arrays)
    datas = [a._data if isinstance(a, NDArray) else a for a in arrays]
    t0 = _perf_counter() if _profile_hook is not None else None
    out, node = autograd.invoke(raw_fn, datas, parents, name)
    if t0 is not None:
        _profile_hook(name, _perf_counter() - t0)
    # results take the class of the first DENSE array input, so mx.np
    # arrays propagate through every op; sparse inputs densify (their
    # constructors need companion arrays, and op results are dense)
    cls = next((type(a) for a in arrays
                if isinstance(a, NDArray) and a.stype == "default"),
               NDArray)
    if n_out == 1:
        res = cls(out)
        if node is not None:
            res._ag = (node, 0)
        if _NAIVE:
            res._data.block_until_ready()
        return res
    results = []
    for i, o in enumerate(out):
        r = cls(o)
        if node is not None:
            r._ag = (node, i)
        results.append(r)
    if _NAIVE:
        for r in results:
            r._data.block_until_ready()
    return tuple(results)


class NDArray:
    """Multi-dimensional, asynchronously-evaluated array."""

    __slots__ = ("_data", "_ag", "_ag_leaf", "grad", "_version")
    __array_priority__ = 1000.0

    def __init__(self, data):
        self._data = data          # jax.Array
        self._ag = None            # (Node, out_index) when produced on tape
        self._ag_leaf = None       # autograd.Leaf when attach_grad()'d
        self.grad = None           # NDArray grad buffer
        self._version = 0

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self) -> int:
        return int(self._data.size)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def context(self) -> Context:
        try:
            dev = next(iter(self._data.devices()))
        except Exception:
            return current_context()
        return Context("cpu" if dev.platform == "cpu" else "tpu", dev.id)

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return apply_op(lambda x: x.T, [self], "T")

    # -- sync / host interop ------------------------------------------------
    def wait_to_read(self) -> None:
        self._data.block_until_ready()

    wait_to_write = wait_to_read

    def asnumpy(self) -> _np.ndarray:
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return f"{self.asnumpy()!r}\n<NDArray {self.shape} @{self.context}>"

    def __reduce__(self):
        # pickle via host numpy (optimizer-state checkpoints, kvstore)
        return (_unpickle_ndarray, (self.asnumpy(),))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- dtype / device movement -------------------------------------------
    def astype(self, dtype, copy: bool = True) -> "NDArray":
        dt = dtype_np(dtype)
        if not copy and self.dtype == dt:
            return self
        return apply_op(lambda x: x.astype(dt), [self], "astype")

    def as_in_context(self, ctx: Context) -> "NDArray":
        dev = ctx.jax_device()
        if dev in self._data.devices():
            return self
        return type(self)(jax.device_put(self._data, dev))

    as_in_ctx = as_in_context

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        if isinstance(other, Context):
            return type(self)(jax.device_put(self._data, other.jax_device()))
        other._set_data(jnp.asarray(self._data, other._data.dtype))
        return other

    def copy(self) -> "NDArray":
        return type(self)(self._data + 0 if self._data.dtype != jnp.bool_
                          else self._data.copy())

    def detach(self) -> "NDArray":
        return type(self)(self._data)

    def to_dlpack(self):
        return jax.dlpack.to_dlpack(self._data)

    # -- mutation -----------------------------------------------------------
    def _set_data(self, new_data) -> None:
        """Rebind the buffer (the 'write' side of the engine variable)."""
        if autograd.is_recording() and self._ag is not None:
            raise MXNetError(
                "in-place write to an array produced under autograd.record() "
                "is not allowed (it would invalidate the tape)")
        self._data = new_data
        self._ag = None
        self._version += 1

    @staticmethod
    def _norm_key(key):
        """NumPy accepts plain lists as advanced indices (``x[[0, 2]]``,
        ``x[1, :, [0, 4]]``); jax insists on arrays — normalize. An
        EMPTY list must become an int indexer (jnp.asarray([]) is
        float32, which jax rejects; numpy's x[[]] selects nothing)."""
        def as_idx(seq):
            a = jnp.asarray(seq)
            return a.astype(jnp.int32) if a.size == 0 else a
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, list):
            return as_idx(key)
        if isinstance(key, tuple):
            return tuple(
                k._data if isinstance(k, NDArray)
                else as_idx(k) if isinstance(k, list) else k
                for k in key)
        return key

    def __setitem__(self, key, value) -> None:
        if isinstance(value, NDArray):
            value = value._data
        key = self._norm_key(key)
        if key is None or key is Ellipsis or \
                (isinstance(key, slice) and key == slice(None)):
            if _np.isscalar(value):
                self._set_data(jnp.full(self.shape, value, self._data.dtype))
            else:
                v = jnp.asarray(value, self._data.dtype)
                self._set_data(jnp.broadcast_to(v, self.shape))
        else:
            self._set_data(self._data.at[key].set(value))

    def __getitem__(self, key) -> "NDArray":
        key = self._norm_key(key)
        return apply_op(lambda x: x[key], [self], "getitem")

    # -- autograd -----------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None) -> None:
        """Allocate a gradient buffer and mark this array as a variable."""
        self.grad = type(self)(jnp.zeros(self.shape, self._data.dtype))
        self._ag_leaf = autograd.Leaf(self, grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad], retain_graph, train_mode)

    # -- arithmetic (each funnels through apply_op) --------------------------
    def _binop(self, other, fn, name):
        if isinstance(other, NDArray):
            return apply_op(fn, [self, other], name)
        return apply_op(lambda x: fn(x, other), [self], name)

    def _rbinop(self, other, fn, name):
        return apply_op(lambda x: fn(other, x), [self], name)

    def __add__(self, o): return self._binop(o, jnp.add, "add")
    def __radd__(self, o): return self._rbinop(o, jnp.add, "add")
    def __sub__(self, o): return self._binop(o, jnp.subtract, "sub")
    def __rsub__(self, o): return self._rbinop(o, jnp.subtract, "rsub")
    def __mul__(self, o): return self._binop(o, jnp.multiply, "mul")
    def __rmul__(self, o): return self._rbinop(o, jnp.multiply, "mul")
    def __truediv__(self, o): return self._binop(o, jnp.divide, "div")
    def __rtruediv__(self, o): return self._rbinop(o, jnp.divide, "rdiv")
    def __mod__(self, o): return self._binop(o, jnp.mod, "mod")
    def __rmod__(self, o): return self._rbinop(o, jnp.mod, "rmod")
    def __pow__(self, o): return self._binop(o, jnp.power, "pow")
    def __rpow__(self, o): return self._rbinop(o, jnp.power, "rpow")
    def __matmul__(self, o): return self._binop(o, jnp.matmul, "matmul")
    def __neg__(self): return apply_op(jnp.negative, [self], "neg")
    def __abs__(self): return apply_op(jnp.abs, [self], "abs")

    def __eq__(self, o): return self._binop(o, lambda a, b: (a == b).astype(a.dtype), "eq")
    def __ne__(self, o): return self._binop(o, lambda a, b: (a != b).astype(a.dtype), "ne")
    def __gt__(self, o): return self._binop(o, lambda a, b: (a > b).astype(a.dtype), "gt")
    def __ge__(self, o): return self._binop(o, lambda a, b: (a >= b).astype(a.dtype), "ge")
    def __lt__(self, o): return self._binop(o, lambda a, b: (a < b).astype(a.dtype), "lt")
    def __le__(self, o): return self._binop(o, lambda a, b: (a <= b).astype(a.dtype), "le")

    __hash__ = object.__hash__

    # in-place operators rebind the buffer (engine-var write analogue)
    def __iadd__(self, o):
        self._set_data(self._data + (o._data if isinstance(o, NDArray) else o))
        return self

    def __isub__(self, o):
        self._set_data(self._data - (o._data if isinstance(o, NDArray) else o))
        return self

    def __imul__(self, o):
        self._set_data(self._data * (o._data if isinstance(o, NDArray) else o))
        return self

    def __itruediv__(self, o):
        self._set_data(self._data / (o._data if isinstance(o, NDArray) else o))
        return self

    # -- shape manipulation / reductions (method forms) ----------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = tuple(int(s) for s in shape)
        # MXNet magic values: -1 infer (same as numpy), 0 copy-from-input
        if 0 in shape:
            shape = tuple(self.shape[i] if s == 0 else s
                          for i, s in enumerate(shape))
        return apply_op(lambda x: jnp.reshape(x, shape), [self], "reshape")

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, axes=None):
        return apply_op(lambda x: jnp.transpose(x, axes), [self], "transpose")

    def swapaxes(self, a1, a2):
        return apply_op(lambda x: jnp.swapaxes(x, a1, a2), [self], "swapaxes")

    def flatten(self):
        n = self.shape[0] if self.ndim > 0 else 1
        return self.reshape(n, -1)

    def expand_dims(self, axis):
        return apply_op(lambda x: jnp.expand_dims(x, axis), [self], "expand_dims")

    def squeeze(self, axis=None):
        return apply_op(lambda x: jnp.squeeze(x, axis), [self], "squeeze")

    def broadcast_to(self, shape):
        return apply_op(lambda x: jnp.broadcast_to(x, shape), [self], "broadcast_to")

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def _reduce(self, fn, axis, keepdims, name):
        return apply_op(lambda x: fn(x, axis=axis, keepdims=keepdims),
                        [self], name)

    def sum(self, axis=None, keepdims=False):
        return self._reduce(jnp.sum, axis, keepdims, "sum")

    def mean(self, axis=None, keepdims=False):
        return self._reduce(jnp.mean, axis, keepdims, "mean")

    def max(self, axis=None, keepdims=False):
        return self._reduce(jnp.max, axis, keepdims, "max")

    def min(self, axis=None, keepdims=False):
        return self._reduce(jnp.min, axis, keepdims, "min")

    def prod(self, axis=None, keepdims=False):
        return self._reduce(jnp.prod, axis, keepdims, "prod")

    def norm(self, ord=2, axis=None, keepdims=False):
        return apply_op(
            lambda x: jnp.linalg.norm(x.reshape(-1) if axis is None else x,
                                      ord=ord, axis=axis, keepdims=keepdims),
            [self], "norm")

    def argmax(self, axis=None, keepdims=False):
        return apply_op(
            lambda x: jnp.argmax(x, axis=axis, keepdims=keepdims)
            .astype(jnp.float32), [self], "argmax")

    def argmin(self, axis=None, keepdims=False):
        return apply_op(
            lambda x: jnp.argmin(x, axis=axis, keepdims=keepdims)
            .astype(jnp.float32), [self], "argmin")

    def clip(self, a_min=None, a_max=None):
        return apply_op(lambda x: jnp.clip(x, a_min, a_max), [self], "clip")

    def abs(self):
        return apply_op(jnp.abs, [self], "abs")

    def sqrt(self):
        return apply_op(jnp.sqrt, [self], "sqrt")

    def exp(self):
        return apply_op(jnp.exp, [self], "exp")

    def log(self):
        return apply_op(jnp.log, [self], "log")

    def relu(self):
        return apply_op(jax.nn.relu, [self], "relu")

    def sigmoid(self):
        return apply_op(jax.nn.sigmoid, [self], "sigmoid")

    def tanh(self):
        return apply_op(jnp.tanh, [self], "tanh")

    def softmax(self, axis=-1):
        return apply_op(lambda x: jax.nn.softmax(x, axis=axis), [self], "softmax")

    def slice_axis(self, axis, begin, end):
        def _f(x):
            idx = [slice(None)] * x.ndim
            idx[axis] = slice(begin, end)
            return x[tuple(idx)]
        return apply_op(_f, [self], "slice_axis")

    def take(self, indices, axis=0):
        idx = indices._data if isinstance(indices, NDArray) else indices
        return apply_op(
            lambda x: jnp.take(x, idx.astype(jnp.int32), axis=axis),
            [self], "take")

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return apply_op(
            lambda x: jax.nn.one_hot(x.astype(jnp.int32), depth) *
            (on_value - off_value) + off_value, [self], "one_hot")

    def tile(self, reps):
        return apply_op(lambda x: jnp.tile(x, reps), [self], "tile")

    def repeat(self, repeats, axis=None):
        return apply_op(lambda x: jnp.repeat(x, repeats, axis=axis),
                        [self], "repeat")

    def pad(self, *a, **kw):
        from . import ops
        return ops.pad(self, *a, **kw)

    def dot(self, other):
        from . import ops
        return ops.dot(self, other)

    def zeros_like(self):
        return type(self)(jnp.zeros_like(self._data))

    def ones_like(self):
        return type(self)(jnp.ones_like(self._data))

    def asfloat(self):
        return self.astype("float32")

    def tostype(self, stype):
        if stype != "default":
            raise NotImplementedError("sparse storage handled by mxtpu.sparse")
        return self


def _unpickle_ndarray(np_val):
    return NDArray(jnp.asarray(np_val))


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------
def _device(ctx: Optional[Context]):
    return (ctx or current_context()).jax_device()


def from_jax(x) -> NDArray:
    return NDArray(x)


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(source, NDArray):
        source = source._data
    if dtype is None:
        if isinstance(source, jax.Array):
            dtype = source.dtype
        elif isinstance(source, _np.ndarray):
            # reference semantics (python/mxnet/ndarray/ndarray.py array()):
            # float32 default unless the source is an NDArray; integer/bool
            # numpy inputs keep their dtype (indexing use-cases)
            dtype = source.dtype if source.dtype.kind in "iub" \
                else _np.float32
        else:
            dtype = _np.float32
    np_val = _np.asarray(source, dtype_np(dtype))
    return NDArray(jax.device_put(np_val, _device(ctx)))


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(_device(ctx)):
        return NDArray(jnp.zeros(shape, dtype_np(dtype)))


def ones(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(_device(ctx)):
        return NDArray(jnp.ones(shape, dtype_np(dtype)))


def full(shape, val, ctx=None, dtype=None) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(_device(ctx)):
        return NDArray(jnp.full(shape, val, dtype_np(dtype)))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    with jax.default_device(_device(ctx)):
        out = jnp.arange(start, stop, step, dtype_np(dtype))
        if repeat > 1:
            out = jnp.repeat(out, repeat)
        return NDArray(out)


def concat(*arrays, dim: int = 1) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return apply_op(lambda *xs: jnp.concatenate(xs, axis=dim),
                    list(arrays), "concat")


def stack(*arrays, axis: int = 0) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return apply_op(lambda *xs: jnp.stack(xs, axis=axis),
                    list(arrays), "stack")


def waitall() -> None:
    """Block until all queued computation completes (Engine::WaitForAll).

    A device runs its programs in the order they were enqueued, so
    waiting on a fresh trivial program on each device awaits everything
    queued before it.
    """
    for dev in jax.local_devices():
        (jax.device_put(0, dev) + 0).block_until_ready()


# ---------------------------------------------------------------------------
# serialization — reference NDArray::Save/Load container (.params files,
# src/ndarray/ndarray.cc). We keep the user API; mxtpu.serde implements the
# binary format.
# ---------------------------------------------------------------------------
def save(fname: str, data) -> None:
    from ..serde import save_ndarrays
    save_ndarrays(fname, data)


def load(fname: str):
    from ..serde import load_ndarrays
    return load_ndarrays(fname)
