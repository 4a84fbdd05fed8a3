"""The trace names what it shows (ISSUE 25): stable module names for the
watched programs, the scope map read from the compiled program, the
program's spans in the profiler's own trace, the engine loop's phase
spans, the TTFT split, and the flight ring kept for rare records.

Engines use the standard tier-1 shape (``llama_refs.engine_factory``).
"""
import glob
import os
import threading
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import llama_refs
from mxtpu import telemetry
from mxtpu.models import llama
from mxtpu.parallel import mesh as pmesh, step as pstep
from mxtpu.serve import Request
from mxtpu.telemetry import scopes as tscopes

PHASES = ("serve.sweep_pick", "serve.admit", "serve.decode_step",
          "serve.readback", "serve.emit")
PHASE_HISTS = ("span_serve_sweep_pick_ms", "span_serve_admit_ms",
               "span_serve_decode_dispatch_ms", "span_serve_readback_ms",
               "span_serve_emit_ms")
TTFT_HISTS = ("serve_ttft_queue_ms", "serve_ttft_admit_ms",
              "serve_ttft_first_wait_ms")


def hist(name):
    """(sum, count) of a registry histogram, over its label sets."""
    total = count = 0.0
    for line in telemetry.prometheus().splitlines():
        head, _, value = line.rpartition(" ")
        series = head.split("{", 1)[0]
        if series == f"mxtpu_{name}_sum":
            total += float(value)
        elif series == f"mxtpu_{name}_count":
            count += float(value)
    return total, count


def submit_some(eng, n, mnew=6, **kw):
    for i in range(n):
        eng.submit(Request(np.arange(1, 4 + 2 * (i % 3)), mnew, seed=i,
                           **kw))


@pytest.fixture(scope="module")
def paged_run(serve_cfg, serve_params):
    """One paged engine, run once with sampled traffic over two prefill
    buckets; the programs it compiled are in the catalog."""
    eng = llama_refs.engine_factory(serve_cfg, serve_params,
                                    page_size=4)()
    submit_some(eng, 4, temperature=0.7, top_p=0.9)
    eng.run()
    return eng


# -- 1. program names ------------------------------------------------------
def test_watched_programs_compile_under_stable_names(serve_cfg,
                                                     serve_params):
    """The names the benchmark's configs match programs by."""
    eng = llama_refs.engine_factory(serve_cfg, serve_params,
                                    page_size=4)()
    submit_some(eng, 3)
    eng.run()
    cat = telemetry.programs()
    assert cat["serve_decode"].module == "jit_decode_slots_paged"
    buckets = sorted(eng._prefills)
    assert len(buckets) >= 2
    # one module name per bucket, none shared, none anonymous
    names = {cat[f"serve_prefill_b{b}"].module for b in buckets}
    assert names == {f"jit_prefill_slot_paged_b{b}" for b in buckets}
    for p in cat.values():
        assert "unknown" not in p.module and "lambda" not in p.module


def test_copy_page_and_train_step_names(paged_run):
    eng = paged_run
    kv = jax.tree.map(jnp.zeros_like, eng._kv)
    eng._copy_fn(kv, np.int32(1), np.int32(2))
    assert telemetry.programs()["serve_copy_page"].module == "jit_copy_page"
    # two engines never share a jit cache (compile_count's churn gate)
    other = llama_refs.engine_factory(
        eng.cfg, eng.params, page_size=4)()
    assert other._copy_fn._cache_size() == 0
    assert eng._copy_fn._cache_size() == 1


def test_watch_jit_names_any_callable():
    w = telemetry.watch_jit(lambda x: x + 1, "toy_watch", "toy_program",
                            expected=None)
    assert int(w(jnp.int32(1))) == 2
    p = telemetry.programs()["toy_watch"]
    assert (p.name, p.module) == ("toy_watch", "jit_toy_program")


# -- 2. the scope map ------------------------------------------------------
def test_scope_path_takes_machinery_off():
    sp = tscopes.scope_path
    assert sp("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
              "rematted_computation/mlp/dot_general") == ("mlp", True)
    assert sp("jit(f)/jvp()/while/body/closed_call/checkpoint/mlp/"
              "dot_general") == ("mlp", False)
    assert sp("jit(d)/sampler/jit(argsort)/sort") == ("sampler", False)
    assert sp("jit(d)/vmap(sampler)/jit(_gumbel)/log") == ("sampler", False)
    assert sp("jit(d)/transpose(jvp(xent))/mul") == ("xent", False)
    assert sp("jit(f)/while/body/dynamic_slice") == ("", False)
    assert sp("jit(d)/attention/kv_gather/gather") == \
        ("attention/kv_gather", False)
    # XLA's merged names: common prefix, then each one's tail
    assert sp("jit(d)/while/body/squeeze;qkv_proj/transpose;qkv_proj/"
              "reshape") == ("qkv_proj", False)
    assert sp("lt") == ("", False) and sp("") == ("", False)


def test_scope_map_reads_fusions_through_their_computation():
    text = """HloModule jit_toy, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(toy)/sampler/neg"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="a"}
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  ROOT %copy.2 = f32[4]{0} copy(%fusion.7)
}
"""
    module, scopes = tscopes.scope_map(text)
    assert module == "jit_toy"
    assert scopes["neg.1"] == ("sampler", False)
    assert scopes["fusion.7"] == ("sampler", False)    # from its root
    assert scopes["copy.2"] == ("", False)


@pytest.mark.parametrize("scope", ["sampler", "kv_gather", "attention",
                                   "kv_write", "qkv_proj", "mlp", "norm"])
def test_decode_program_map_holds_the_model_scopes(paged_run, scope):
    scopes = telemetry.programs()["serve_decode"].scopes
    first = {path.split("/")[0] for path, _ in scopes.values()}
    assert scope in first, sorted(first)
    assert "" in first                    # the scan's own slicing


def test_remat_train_step_marks_rematerialised_instructions():
    cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32,
                  attn_impl="dense", remat=True)
    mesh = pmesh.create_mesh(fsdp=1, devices=jax.devices()[:1])
    rules = llama.sharding_rules(cfg)
    tx = optax.sgd(1e-3)
    state = pstep.init_state(
        llama.init_params(cfg, jax.random.PRNGKey(0)), tx, mesh, rules)
    step = pstep.make_train_step(llama.loss_fn(cfg, mesh=mesh), tx, mesh,
                                 rules)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 16)), jnp.int32)
    state, loss = step(state, {"tokens": tokens})
    assert np.isfinite(float(loss))
    prog = telemetry.programs()["train_step"]
    assert prog.module == "jit_train_step"
    remat = {path.split("/")[0] for path, r in prog.scopes.values() if r}
    plain = {path.split("/")[0] for path, r in prog.scopes.values()
             if not r}
    # the layer's pieces run again in the backward pass; the loss's
    # scope is outside the checkpointed layer and never does
    assert {"mlp", "attention", "qkv_proj"} <= remat, sorted(remat)
    assert "xent" in plain and "xent" not in remat


# -- 3. spans in the profiler's trace --------------------------------------
def test_spans_are_in_the_profilers_trace_on_its_clock(serve_cfg,
                                                       serve_params,
                                                       tmp_path):
    from jax.profiler import ProfileData
    eng = llama_refs.engine_factory(serve_cfg, serve_params,
                                    page_size=4)()
    submit_some(eng, 2)
    eng.run()                                # compiled, warm
    submit_some(eng, 2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test_enclosing"):
            eng.run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    host, ops = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name in PHASES or ev.name in ("test_enclosing",
                                                    "serve.prefill"):
                    host.setdefault(ev.name, []).append(span)
                elif "hlo_op" in dict(ev.stats):
                    ops.append(span)
    (w0, w1), = host["test_enclosing"]
    for name in PHASES + ("serve.prefill",):
        assert host.get(name), (name, sorted(host))
        assert all(w0 <= a and b <= w1 for a, b in host[name]), name
    # one time base: the XLA operations the run executed lie inside the
    # same enclosing annotation as the spans that dispatched them
    inside = [1 for a, b in ops if w0 <= a and b <= w1]
    assert ops and len(inside) >= 0.9 * len(ops)
    # the prefill span nests in the admit phase
    a0, a1 = host["serve.prefill"][0]
    assert any(s <= a0 and a1 <= e for s, e in host["serve.admit"])


# -- 4. the phases cover the loop ------------------------------------------
def test_phase_histograms_add_to_the_loops_wall_time(serve_cfg,
                                                     serve_params):
    """The five phase spans tile the loop: disjoint, so their sums
    never exceed the loop's wall time, and nothing that takes time
    lies between them. The toy step takes 0.9 ms on the CPU, of which
    the spans' own bookkeeping between one's end and the next one's
    start is a tenth; 10 ms slept inside the dispatch put the step
    where the phases, not their seams, are what is measured (the chat
    cell's step is 30 ms). The seams are the scheduler's to stretch
    (5.7% of a 4 ms step under six test workers), so the floor is four
    fifths and no nearer: it catches spans that stopped covering the
    step's work, the ceiling spans that overlap."""
    eng = llama_refs.engine_factory(serve_cfg, serve_params,
                                    page_size=4)()
    submit_some(eng, 2)
    eng.run()                                # compiles stay out of it
    decode = eng._decode

    def slow_decode(*args):
        out = decode(*args)
        time.sleep(0.01)
        return out
    eng._decode = slow_decode
    before = [hist(n) for n in PHASE_HISTS]
    submit_some(eng, 2, mnew=24)
    steps0 = eng.steps_run
    t0 = time.perf_counter()
    eng.run()
    wall_ms = 1e3 * (time.perf_counter() - t0)
    assert eng.steps_run - steps0 >= 20
    after = [hist(n) for n in PHASE_HISTS]
    sums = [a[0] - b[0] for a, b in zip(after, before)]
    counts = [a[1] - b[1] for a, b in zip(after, before)]
    assert min(counts) >= 20
    assert 0.8 * wall_ms <= sum(sums) <= wall_ms, (sums, wall_ms)


# -- 5. TTFT split ---------------------------------------------------------
def test_ttft_parts_add_to_the_gateways_ttft(serve_cfg, serve_params):
    from mxtpu.serve.gateway import Gateway
    gw = Gateway(llama_refs.engine_factory(serve_cfg, serve_params,
                                           page_size=4),
                 n_replicas=1, queue_max=64)
    try:
        before = {n: hist(n) for n in TTFT_HISTS + ("gateway_ttft_ms",)}
        handles = [gw.submit(np.arange(1, 5 + i % 3), 4, seed=i)
                   for i in range(6)]
        for h in handles:
            assert len(h.result(120)) == 4 and h.reason == "complete"
        delta = {n: tuple(a - b for a, b in zip(hist(n), before[n]))
                 for n in before}
    finally:
        gw.close()
    counts = {n: c for n, (_, c) in delta.items()}
    assert set(counts.values()) == {6.0}, counts
    engine = sum(delta[n][0] for n in TTFT_HISTS)
    total = delta["gateway_ttft_ms"][0]
    # the gateway's share is what is left: never negative, and small
    # beside the engine's three parts
    assert 0.0 <= total - engine <= 0.2 * total, (engine, total)
    assert all(delta[n][0] > 0 for n in TTFT_HISTS)


def test_ttft_parts_are_observed_once_per_request(serve_cfg, serve_params):
    eng = llama_refs.engine_factory(serve_cfg, serve_params)()
    before = [hist(n)[1] for n in TTFT_HISTS]
    submit_some(eng, 3)
    out = eng.run()
    assert all(len(t) == 6 for t in out.values())
    assert [hist(n)[1] - b for n, b in zip(TTFT_HISTS, before)] == [3.0] * 3


# -- 6. the flight ring keeps what it is for -------------------------------
def test_recompile_record_survives_a_thousand_decode_steps(serve_cfg,
                                                           serve_params):
    eng = llama_refs.engine_factory(serve_cfg, serve_params)()
    submit_some(eng, 2)
    eng.run()
    telemetry.flight().record("recompile", "serve_decode", key="(test)",
                              cache_size=2, expected=1)
    steps0 = eng.steps_run
    while eng.steps_run - steps0 < 1000:
        submit_some(eng, 2, mnew=26)
        eng.run()
    kinds = [(e["kind"], e["name"]) for e in telemetry.flight().tail(512)]
    assert ("recompile", "serve_decode") in kinds
    # per-request spans still land there; per-step spans do not
    assert ("span", "serve.prefill") in kinds
    assert not any(k == "span" and n in PHASES for k, n in kinds)


def test_span_factory_flight_argument():
    ring0 = len(telemetry.flight())
    with telemetry.span_factory("toy.loud")():
        pass
    with telemetry.span_factory("toy.quiet", flight=False)():
        pass
    names = [e["name"] for e in telemetry.flight().tail(8)]
    assert "toy.loud" in names and "toy.quiet" not in names
    assert len(telemetry.flight()) <= ring0 + 1 or ring0 == 512
    # both still time themselves and feed their histograms
    assert hist("span_toy_quiet_ms")[1] == 1.0


def test_threads_each_nest_their_own_spans():
    """A span is a TraceAnnotation too: entering and leaving from two
    threads at once must leave both threads' depth at zero."""
    make = telemetry.span_factory("toy.thread", flight=False)
    depths = []

    def work():
        for _ in range(200):
            with make():
                with make():
                    pass
        depths.append(telemetry.current_depth())
    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert depths == [0, 0]
