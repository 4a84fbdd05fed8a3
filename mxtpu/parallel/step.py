"""Sharded training step — the rebuild of the reference's distributed
epoch body (``Module.fit`` forward/backward/update over
DataParallelExecutorGroup + KVStore push/pull, SURVEY.md §3.3/§3.4).

Where the reference pushed per-parameter gradients through KVStore and
ran optimizer ops on servers/devices, here the WHOLE step — forward,
backward, gradient allreduce, optimizer update — is one jitted XLA
program over the mesh. Gradient reduction is implicit: params are
replicated (or fsdp-sharded) while the batch is dp-sharded, so XLA
inserts the psum/reduce-scatter on the backward pass, laid on ICI.

Buffers are donated (params, optimizer state) so the update is in-place
in HBM — the rebuild of MXNet's mutable in-place ``sgd_update``.
"""
from __future__ import annotations

import contextlib
import contextvars
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from .sharding import (ShardingRules, batch_spec, bytes_per_device,
                       key_str)

__all__ = ["TrainState", "init_state", "make_train_step", "make_eval_step",
           "traced_state_bytes"]

_state_bytes = contextvars.ContextVar("train_state_bytes", default=None)


def traced_state_bytes() -> Optional[int]:
    """While :func:`make_train_step`'s step is being traced: the bytes
    one device holds of the state it was handed (parameters, optimizer
    state, model state, as the rule table lays them out), counted from
    shapes, so it is the same for a state that is resident and for one
    that is only described. ``None`` outside such a trace. A model reads
    it to size what its backward pass keeps (``models/llama.py``
    ``remat_plan``)."""
    return _state_bytes.get()


@contextlib.contextmanager
def _tracing(state: "TrainState", tx, mesh: Mesh, rules: ShardingRules):
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tuple(state))
    params, opt_state, step, mstate = shapes
    token = _state_bytes.set(
        bytes_per_device(params, rules.tree_specs(params), mesh)
        + bytes_per_device(
            opt_state, opt_state_shardings(tx, params, mesh, rules), mesh)
        + bytes_per_device(mstate, rules.tree_specs(mstate), mesh)
        + step.dtype.itemsize)
    try:
        yield
    finally:
        _state_bytes.reset(token)


class TrainState(NamedTuple):
    """Functional training state (params + optimizer state + step +
    non-differentiable model state, e.g. BatchNorm running stats — the
    reference's mutable aux params, threaded functionally)."""
    params: Any
    opt_state: Any
    step: Any
    model_state: Any = ()

    @classmethod
    def create(cls, params: Any, tx, model_state: Any = ()) -> "TrainState":
        return cls(params=params, opt_state=tx.init(params),
                   step=jnp.zeros((), jnp.int32), model_state=model_state)


def _path_str(path) -> tuple:
    return tuple(key_str(k) for k in path)


def opt_state_shardings(tx, params: Any, mesh: Mesh,
                        rules: ShardingRules):
    """Sharding tree for ``tx.init(params)``: optax states embed the
    params pytree verbatim (Adam mu/nu etc.), so an opt-state leaf whose
    tree path ends with a parameter's path (and matches its shape) gets
    that parameter's sharding; everything else (counts, scalars)
    replicates. No data-dependency means XLA can't propagate this on
    its own — it must be explicit."""
    pspecs = rules.tree_specs(params)
    plist = []
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(pspecs)[0]):
        plist.append((_path_str(path), getattr(leaf, "shape", ()), spec))

    abs_opt = jax.eval_shape(tx.init, params)

    def spec_for(path, leaf):
        p = _path_str(path)
        for ppath, pshape, pspec in plist:
            if (len(p) >= len(ppath) and p[-len(ppath):] == ppath
                    and leaf.shape == pshape):
                return NamedSharding(mesh, pspec)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, abs_opt)


@telemetry.setup_phase("state_alloc")
def init_state(params: Any, tx, mesh: Mesh,
               rules: ShardingRules, model_state: Any = ()) -> TrainState:
    """Place params per the rule table and build the optimizer state
    sharded to match (per-param moments inherit their parameter's
    sharding; scalars replicate). ``model_state`` (BN running stats etc.)
    is placed by the same rule table — typically replicated."""
    pspecs = rules.tree_specs(params)
    # copy ON the target sharding: the train step donates the state (so
    # the caller's arrays must never be aliased), and the copy must not
    # stage through a single device — an fsdp/tp-sharded param larger
    # than one device's HBM has to materialize directly sharded.
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                             is_leaf=lambda s: isinstance(s, P))
    params = jax.jit(lambda t: jax.tree.map(jnp.copy, t),
                     out_shardings=shardings)(params)
    oshard = opt_state_shardings(tx, params, mesh, rules)
    opt_state = jax.jit(tx.init, out_shardings=oshard)(params)
    step = jax.device_put(jnp.zeros((), jnp.int32),
                          NamedSharding(mesh, P()))
    if model_state != ():
        msharding = jax.tree.map(
            lambda s: NamedSharding(mesh, s), rules.tree_specs(model_state),
            is_leaf=lambda s: isinstance(s, P))
        model_state = jax.jit(lambda t: jax.tree.map(jnp.copy, t),
                              out_shardings=msharding)(model_state)
    # HBM ledger: the training state's resident footprint (one trainer
    # per process is the deployed shape, so fixed names last-write-win)
    from ..telemetry import perfscope
    perfscope.ledger().account_tree("params", params, name="train")
    perfscope.ledger().account_tree("optimizer", opt_state, name="train")
    if model_state != ():
        perfscope.ledger().account_tree("workspace", model_state,
                                        name="train_model_state")
    return TrainState(params, opt_state, step, model_state)


@telemetry.setup_phase("step_build")
def make_train_step(loss_fn: Callable[..., Any], tx, mesh: Mesh,
                    rules: Optional[ShardingRules] = None,
                    has_rng: bool = False,
                    grad_accum: int = 1,
                    loss_has_aux: bool = False,
                    has_state: bool = False,
                    skip_nonfinite: bool = False):
    """Build the jitted sharded step.

    ``loss_fn(params, batch[, rng]) -> loss`` (or ``(loss, aux)`` with
    ``loss_has_aux``). With ``has_state``, ``loss_fn(params, model_state,
    batch[, rng]) -> (loss, new_model_state)`` and the state threads
    through ``TrainState.model_state`` across steps (BatchNorm running
    stats — the reference's aux params). ``tx`` is an optax
    GradientTransformation. Returns ``step(state, batch[, rng]) ->
    (state, loss[, aux])``; ``state`` is donated.

    ``skip_nonfinite=True`` generalizes the AMP dynamic-loss-scaling
    overflow skip to plain (non-AMP) training: a step whose loss or
    any gradient leaf is inf/nan applies NO update — params, opt
    state, model state, and the step counter all keep their old
    values inside the same XLA program (a ``where`` select, no host
    round-trip), exactly the fused-step AMP semantics where a skipped
    step "never happened". The step then returns an extra trailing
    ``skipped`` bool scalar — ``(state, loss[, aux], skipped)`` — so
    the driver can count skips (``train_nonfinite_skips_total``).
    """
    if has_state and loss_has_aux:
        raise ValueError("has_state already uses the aux slot for "
                         "model_state; fold extra aux into it")
    rules = rules or ShardingRules([(r".*", P())])
    # with accumulation the leading batch dim is the microbatch index
    # (scanned over); the dp sharding moves to dim 1
    bspec = (P(None, *batch_spec(mesh)) if grad_accum > 1
             else batch_spec(mesh))
    bsharding = NamedSharding(mesh, bspec)
    has_aux = loss_has_aux or has_state

    def _loss(params, batch, rng, mstate):
        if has_state:
            return loss_fn(params, mstate, batch, rng) if has_rng \
                else loss_fn(params, mstate, batch)
        return loss_fn(params, batch, rng) if has_rng \
            else loss_fn(params, batch)

    grad_fn = jax.value_and_grad(_loss, has_aux=has_aux)

    def _step(state: TrainState, batch, rng):
        with _tracing(state, tx, mesh, rules):
            return _traced_step(state, batch, rng)

    def _traced_step(state: TrainState, batch, rng):
        mstate = state.model_state
        if grad_accum > 1:
            def body(carry, xs):
                i, mb = xs
                loss_acc, grad_acc, ms = carry
                # distinct dropout/noise per microbatch, else accumulation
                # is not equivalent to the large batch
                mb_rng = None if rng is None else jax.random.fold_in(rng, i)
                val, grads = grad_fn(state.params, mb, mb_rng, ms)
                loss = val[0] if has_aux else val
                aux = val[1] if has_aux else 0.0
                if has_state:
                    ms, aux = aux, 0.0
                return (loss_acc + loss,
                        jax.tree.map(jnp.add, grad_acc, grads), ms), aux
            zeros = jax.tree.map(jnp.zeros_like, state.params)
            (loss, grads, mstate), auxes = jax.lax.scan(
                body, (jnp.zeros(()), zeros, mstate),
                (jnp.arange(grad_accum), batch))
            loss = loss / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            aux = auxes  # per-microbatch aux, stacked on the leading dim
        else:
            val, grads = grad_fn(state.params, batch, rng, mstate)
            loss, aux = (val if has_aux else (val, None))
            if has_state:
                mstate, aux = aux, None
        # named like the model's own pieces (models/llama.py), so a
        # trace's operations map back to it (telemetry.programs())
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  state.params, updates)
        # pin updated params to the rule-table layout so the state the
        # next step receives is exactly the init_state placement (no
        # XLA re-layout drift across steps)
        params = jax.lax.with_sharding_constraint(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 rules.tree_specs(params),
                                 is_leaf=lambda s: isinstance(s, P)))
        if skip_nonfinite:
            finite = jnp.isfinite(loss)
            for g in jax.tree.leaves(grads):
                finite = finite & jnp.all(jnp.isfinite(g))
            sel = lambda new_v, old_v: jnp.where(finite, new_v, old_v)
            params = jax.tree.map(sel, params, state.params)
            opt_state = jax.tree.map(sel, opt_state, state.opt_state)
            mstate = jax.tree.map(sel, mstate, state.model_state)
            new = TrainState(params, opt_state,
                             state.step + finite.astype(jnp.int32), mstate)
            if loss_has_aux:
                return new, loss, aux, ~finite
            return new, loss, ~finite
        new = TrainState(params, opt_state, state.step + 1, mstate)
        if loss_has_aux:
            return new, loss, aux
        return new, loss

    telemetry.install_compile_listener()
    # watched: every compile is cost-cataloged (program_flops/bytes →
    # roofline class) and every dispatch feeds the live MFU/goodput
    # gauges + step-anomaly detector. expected=None — tests legally
    # run one step fn over several shapes; the serve-style recompile
    # anomaly counter is not this program's contract. The module is
    # jit_train_step in a trace.
    watched = telemetry.watch_jit(
        _step, "train_step", "train_step", expected=None, loop="train",
        in_shardings=(None, bsharding, None), donate_argnums=(0,))
    jitted = watched._fn
    # every step: kept out of the flight ring
    dispatch_span = telemetry.span_factory(
        "train.step_dispatch", "train_dispatch", flight=False)

    def step(state: TrainState, batch, rng=None):
        # host DISPATCH time only (the program runs async) — with the
        # prefetcher's data-wait histogram and the loop's wall clock
        # this is the step-time split docs/observability.md reads:
        # device ≈ wall − data_wait − dispatch
        with dispatch_span():
            return watched(state, batch, rng)

    step._jitted = jitted
    return step


def make_eval_step(apply_fn: Callable, mesh: Mesh):
    """Jitted sharded inference step: batch dp-sharded, params as placed."""
    bsharding = NamedSharding(mesh, batch_spec(mesh))

    @partial(jax.jit, in_shardings=(None, bsharding))
    def step(params, batch):
        return apply_fn(params, batch)

    return step
