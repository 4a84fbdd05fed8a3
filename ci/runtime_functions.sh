#!/usr/bin/env bash
# CI runtime functions — every CI step is a named bash function, runnable
# locally: `ci/runtime_functions.sh <function> [args...]`.
# The reference kept the same pattern in ci/docker/runtime_functions.sh
# (SURVEY.md §4.4) because it makes local repro of any CI step trivial.
set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"

sanity_check() {
    # lint: syntax errors + undefined names only (style is not gated).
    # The py_compile fallback runs ONLY when pyflakes is absent — a
    # pyflakes FAILURE must fail the check.
    if python -c "import pyflakes" 2>/dev/null; then
        python -m pyflakes mxtpu tools benchmark bench.py \
            __graft_entry__.py
    else
        python - << 'PYEOF'
import pathlib, py_compile, sys
bad = 0
for p in pathlib.Path(".").rglob("*.py"):
    if any(s in str(p) for s in (".git/", "example/")):
        continue
    try:
        py_compile.compile(str(p), doraise=True)
    except py_compile.PyCompileError as e:
        print(e); bad += 1
sys.exit(1 if bad else 0)
PYEOF
    fi
    echo "sanity_check: OK"
}

mxlint() {
    # trace-safety + dispatch static analysis (docs/lint.md): the repo
    # must lint clean, and the seeded fixtures must all be flagged (the
    # second half of that contract is the tier-1 tests/test_mxlint.py
    # gate). Stdlib-only — runs in well under a second.
    python -m tools.mxlint mxtpu/ example/
    # the deep pass (lockset/lock-order, determinism, runtime
    # contracts — docs/lint.md §"The deep pass") over the runtime
    # tree, emitting SARIF for PR annotation; render the report with
    # `python tools/diagnose.py lint`
    python -m tools.mxlint --deep --sarif build/mxlint_deep.sarif \
        mxtpu/ tools/ bench.py
}

lockcheck_smoke() {
    # the runtime half of MXL203 (docs/lint.md §lockcheck): replay a
    # gateway replica-kill chaos test with every lock instrumented, in
    # a FRESH process so the factory patch precedes all lock
    # construction; conftest fails the session on any acquisition
    # order contradicting itself or the static lock graph
    # the speculative kill test drives the MULTI-token step path
    # (_build_drafts -> _dispatch -> variable-advance _emit), whose
    # lock choreography differs from plain stepping (ISSUE 19)
    MXTPU_ANALYSIS_LOCKCHECK=1 python -m pytest \
        tests/test_serve_chaos.py::test_replica_kill_poisson_stream_bit_identical \
        tests/test_serve_chaos.py::test_replica_kill_mid_speculative_run_bit_identical \
        -x -q "$@"
}

unittest_cpu_mesh() {
    # the main suite on the virtual 8-device CPU mesh (conftest forces
    # JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8)
    python -m pytest tests/ -x -q "$@"
}

unittest_fast() {
    # skip the slow markers (dist subprocess tests) for a quick signal
    python -m pytest tests/ -x -q -m "not slow" "$@"
}

dist_tests() {
    # multi-process tests only (local tracker forks workers — the
    # reference's tests/nightly/dist_sync_kvstore.py pattern)
    python -m pytest tests/test_tools.py -x -q "$@"
}

fault_tolerance() {
    # the chaos suite (docs/robustness.md): seeded fault injection
    # against the distributed stack, then tools/flakiness_checker.py
    # reruns the WHOLE file over random seeds to prove the chaos is
    # deterministic (a flaky fault-tolerance test is worse than none)
    python -m pytest tests/test_fault_tolerance.py -x -q "$@"
    python tools/flakiness_checker.py tests/test_fault_tolerance.py -n 3
}

multichip_dryrun() {
    # rehearsal of the sharded step on 8 VIRTUAL CPU devices: shows the
    # programs trace, partition and run; says nothing about chips
    # (chip_smoke.py on a multi-chip host does)
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
    echo "multichip_dryrun: OK (virtual CPU devices)"
}

bench_smoke() {
    # run ONE real (tiny) bench step on CPU so jit/shape regressions in
    # the bench path fail CI; also keep the CLI-rejection contract.
    # Full numbers are the driver's job, on the real chip.
    python - << 'PYEOF'
import json, os, subprocess, sys
env = dict(os.environ, JAX_PLATFORMS="cpu")
out = subprocess.run([sys.executable, "bench.py", "bogus"],
                     capture_output=True, text=True, env=env)
assert out.returncode != 0, "bench.py must reject unknown configs"
out = subprocess.run([sys.executable, "bench.py", "smoke"],
                     capture_output=True, text=True, env=env)
assert out.returncode == 0, f"smoke bench failed:\n{out.stderr[-2000:]}"
line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
rec = json.loads(line)
assert rec["value"] > 0, rec
print(f"bench_smoke: OK ({rec['metric']}={rec['value']} {rec['unit']})")
PYEOF
}

serve_smoke() {
    # continuous-batching serving end to end on CPU (docs/serving.md):
    # a tiny config, a seeded arrival stream of mixed lengths through
    # ServeEngine, greedy tokens checked bit-identical against a
    # per-request generate, and the compile bound (buckets + 1 decode
    # program) enforced. The full contract is tier-1 in
    # tests/test_serve.py; this stage proves the engine path works in
    # a fresh process with no pytest fixtures.
    python - << 'PYEOF'
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import jax.numpy as jnp
from dataclasses import replace
from mxtpu.models import llama
from mxtpu.serve import Request, ServeEngine

cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense")
params = llama.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(7)
reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.choice([3, 5, 9]))),
                max_new_tokens=int(rng.choice([2, 4, 6])),
                arrival_step=i // 2, seed=i)
        for i in range(6)]
eng = ServeEngine(cfg, params, max_slots=3, max_len=32, min_bucket=4)
for r in reqs:
    eng.submit(r)
res = eng.run()
assert eng.compile_count <= eng.n_buckets + 2, \
    (eng.compile_count, eng.n_buckets)
for rid, r in enumerate(reqs):
    ref = llama.generate(cfg, params,
                         jnp.asarray(r.prompt, jnp.int32)[None],
                         r.max_new_tokens,
                         rng=jax.random.PRNGKey(r.seed))
    assert np.array_equal(res[rid],
                          np.asarray(ref)[0, len(r.prompt):]), rid
print(f"serve_smoke: OK ({len(reqs)} requests, "
      f"{eng.steps_run} steps, {eng.compile_count} compiles "
      f"<= {eng.n_buckets} buckets + 1)")
PYEOF
}

paged_kv_smoke() {
    # paged KV cache with CoW prefix sharing end to end on CPU
    # (docs/serving.md §Paged KV cache): a shared-system-prompt burst
    # through a paged ServeEngine sized so the POOL (not slots) is the
    # admission bound — every stream must stay bit-identical to
    # generate (zero drops, backpressure only), prefix hits and the
    # boundary-page CoW fork must actually fire, and the paged pool
    # must reach higher slot concurrency than the dense bank it
    # replaced. Then one paged disagg handoff over the page-granular
    # wire. The full contract is tier-1 in tests/test_paged_kv.py;
    # this stage proves it in a fresh process with no pytest fixtures.
    python - << 'PYEOF'
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import threading
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import jax.numpy as jnp
from dataclasses import replace
from mxtpu.models import llama
from mxtpu.serve import Request, ServeEngine

cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense")
params = llama.init_params(cfg, jax.random.PRNGKey(0))

def ref(prompt, mnew, seed):
    out = llama.generate(cfg, params,
                         jnp.asarray(prompt, jnp.int32)[None], mnew,
                         temperature=1.0, rng=jax.random.PRNGKey(seed))
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]

# 4 slots over a pool that holds only ~2 dense slots' worth of pages:
# the burst must queue on pages, drop nothing, and share the prefix
shared = [7, 3, 9, 1, 5, 2, 8, 4, 6]          # 9 toks, ps=8 -> fork
eng = ServeEngine(cfg, params, max_slots=4, max_len=32, min_bucket=4,
                  page_size=8, n_pages=9)
rng = np.random.default_rng(7)
reqs = [(shared + list(rng.integers(0, cfg.vocab_size, 1 + i % 3)),
         int(rng.choice([2, 4, 6])), i) for i in range(6)]
rids = [eng.submit(Request(prompt=p, max_new_tokens=m,
                           temperature=1.0, seed=s))
        for (p, m, s) in reqs]
peak = {"active": 0}
stop = threading.Event()
def poll():
    while not stop.wait(0.004):
        peak["active"] = max(peak["active"],
                             eng.kv_cache_stats()["active"])
t = threading.Thread(target=poll, daemon=True); t.start()
res = eng.run()
stop.set(); t.join(2)
for rid, (p, m, s) in zip(rids, reqs):
    got = [int(x) for x in res[rid]]
    assert got == ref(p, m, s), (rid, got, ref(p, m, s))  # zero drops
st = eng.kv_cache_stats()
assert st["prefix_hits"] >= 1 and st["cow_forks"] >= 1, st
assert st["pages_used"] > 0 and st["active"] == 0, st   # drained
# pool of 8 usable pages = 2 dense slots' worth; sharing + paging
# must have run MORE than 2 streams concurrently at some point
assert peak["active"] > 2, peak
assert eng.compile_count <= eng.n_buckets + 2, \
    (eng.compile_count, eng.n_buckets)

# one paged disagg handoff over the page-granular wire + journal
from mxtpu.serve.gateway.disagg import DisaggBackend
be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1, max_slots=2,
                   max_len=32, min_bucket=4, page_size=8)
try:
    toks, done = [], threading.Event()
    p1 = shared + [11, 12]
    be.route(Request(prompt=p1, max_new_tokens=4, temperature=1.0,
                     seed=0,
                     on_token=lambda rid, t: toks.append(int(t)),
                     on_done=lambda rid, r: done.set()))
    assert done.wait(120) and toks == ref(p1, 4, 0), toks
    assert int(be._m_page_frames.value) >= 2   # 11 toks / ps 8
    assert len(be._journal) == 1
finally:
    be.close()
print(f"paged_kv_smoke: OK ({len(reqs)} shared-prefix requests, "
      f"peak {peak['active']} active on a 2-dense-slot pool, "
      f"{st['prefix_hits']} prefix hits, {st['cow_forks']} CoW forks, "
      f"paged disagg handoff journaled)")
PYEOF
}

paged_kv_slow() {
    # the slow-marked paged heavies (engine bit-exactness with prefix
    # sharing, pool-exhaustion backpressure, int8 pool determinism,
    # the full disagg wire/journal contract) — tier-1 skips slow
    # markers to stay inside its budget, so this stage is their
    # dedicated CI home (ci_all's unittest_cpu_mesh also runs them)
    python -m pytest tests/test_paged_kv.py -x -q -m slow "$@"
}

spec_decode_slow() {
    # the slow-marked speculative-decoding heavies (mixed-config
    # bit-identity, adversarial drafter, accepted-count rng advance,
    # journaled spec resume, spec over shared CoW pages) — tier-1
    # keeps the drafter unit tests and skips slow markers, so this
    # stage is their dedicated CI home (spec_smoke is the fast
    # fresh-process gate)
    python -m pytest tests/test_spec_decode.py -x -q -m slow "$@"
}

spec_smoke() {
    # speculative decoding end to end on CPU (docs/serving.md
    # §Speculative decoding): a shared-prefix burst through a paged
    # engine with speculate_k>0 — greedy AND sampled streams must be
    # bit-identical to per-request generate (the verify oracle's whole
    # contract), the accepted-token rate must beat 1 token/slot-step
    # (speculation actually firing, not just verifying), and the
    # compile count must sit exactly one program over the paged
    # baseline. The full matrix is tier-1 in tests/test_spec_decode.py;
    # this stage proves it in a fresh process with no pytest fixtures.
    python - << 'PYEOF'
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import jax.numpy as jnp
from dataclasses import replace
from mxtpu.models import llama
from mxtpu.serve import Request, ServeEngine

cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense", max_seq_len=256)
params = llama.init_params(cfg, jax.random.PRNGKey(0))

def ref(prompt, mnew, seed, temp):
    out = llama.generate(cfg, params,
                         jnp.asarray(prompt, jnp.int32)[None], mnew,
                         temperature=temp, rng=jax.random.PRNGKey(seed))
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]

# shared-prefix burst: the first two prompts extend [140, 141, 140]
# with its OWN greedy continuation (teacher-forcing — the remaining
# greedy stream is unchanged), so they plateau immediately and the
# n-gram drafter proposes full budgets, AND they span >1 page with a
# non-page-aligned shared prefix, so the second admission forks the
# boundary page (copy_page must compile). Two sampled requests ride
# along to exercise the rng-chain half of the oracle.
warm = [140, 141, 140] + ref([140, 141, 140], 9, 0, 0.0)   # len 12
eng = ServeEngine(cfg, params, max_slots=2, max_len=256, min_bucket=8,
                  page_size=8, speculate_k=4)
reqs = [(warm, 64, 0, 0.0),
        (warm, 64, 1, 0.0),
        ([140, 141, 141], 48, 2, 0.0),
        ([140, 141, 140, 99], 32, 3, 1.0),
        ([140, 141, 141, 7], 32, 4, 0.9)]
rids = [eng.submit(Request(prompt=p, max_new_tokens=m,
                           temperature=t, seed=s))
        for (p, m, s, t) in reqs]
res = eng.run()
for rid, (p, m, s, t) in zip(rids, reqs):
    got = [int(x) for x in res[rid]]
    assert got == ref(p, m, s, t), (rid, got, ref(p, m, s, t))
st = eng.kv_cache_stats()
total = sum(m for (_, m, _, _) in reqs)
per_slot_step = total / eng.steps_run / 2          # 2 slots
assert per_slot_step > 1.0, (total, eng.steps_run)
assert st["spec_accepted"] > 0, st
assert eng.compile_count == eng.n_buckets + 3, \
    (eng.compile_count, eng.n_buckets)   # decode + copy_page + verify
print(f"spec_smoke: OK ({len(reqs)} shared-prefix requests "
      f"bit-identical to generate, {per_slot_step:.2f} accepted "
      f"tok/slot-step, accept rate {st['spec_accept_rate']:.2f}, "
      f"compile count {eng.compile_count} == buckets+3)")
PYEOF
}

gateway_smoke() {
    # the serving TIER end to end in a fresh process (docs/serving.md
    # §gateway): an HTTP gateway over one engine replica, one streamed
    # request checked bit-identical against per-request generate, and
    # a valid Prometheus scrape carrying the gateway gauges. The full
    # contract (2 replicas, Poisson stream, backpressure, deadlines,
    # disaggregated KV handoff, autoscaler) is tier-1 in
    # tests/test_gateway.py; this proves the service path with no
    # pytest fixtures.
    python - << 'PYEOF'
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import jax.numpy as jnp
from dataclasses import replace
from mxtpu.models import llama
from mxtpu.serve import ServeEngine
from mxtpu.serve.gateway import Gateway, GatewayClient

cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense")
params = llama.init_params(cfg, jax.random.PRNGKey(0))
gw = Gateway(lambda: ServeEngine(cfg, params, max_slots=2, max_len=32,
                                 min_bucket=4), n_replicas=1)
port = gw.start_http(port=0)
cli = GatewayClient("127.0.0.1", port)
rng = np.random.default_rng(13)
prompt = rng.integers(0, cfg.vocab_size, 5)
rec = cli.generate(prompt, 4, seed=2)
assert rec["status"] == 200 and rec["reason"] == "complete", rec
ref = llama.generate(cfg, params, jnp.asarray(prompt, jnp.int32)[None],
                     4, rng=jax.random.PRNGKey(2))
assert rec["tokens"] == [int(t) for t in np.asarray(ref)[0, 5:]], rec
status, prom = cli.get_text("/metrics")
assert status == 200
for fam in ("mxtpu_gateway_replicas", "mxtpu_gateway_requests_total",
            "mxtpu_gateway_ttft_ms", "mxtpu_serve_tokens_total"):
    assert f"# TYPE {fam}" in prom, fam
for line in prom.splitlines():
    assert line.startswith("#") or " " in line, line
status, state = cli.get_json("/state")
assert status == 200 and state["n_replicas"] == 1, state
gw.close()
print(f"gateway_smoke: OK (4 streamed tokens bit-identical, "
      f"{len(prom.splitlines())} metric lines, "
      f"{len(state['replicas'])} replica)")
PYEOF
}

fleet_smoke() {
    # the fleet control plane end to end in a fresh process
    # (docs/serving.md §"Fleet control plane"): a two-model fleet
    # gateway behind one HTTP front door, one streamed request per
    # model checked bit-identical against per-request generate (the
    # responses carrying model + build-version labels), one live
    # checkpoint hot-swap with zero dropped requests, and the
    # FEDERATED /metrics scrape validated — per-model series plus a
    # peer process's series under strict Prometheus grammar. The full
    # contract (arbiter chip moves, priority shed ordering, chaos
    # mid-swap) is tier-1 in tests/test_fleet.py; this proves the
    # service path with no pytest fixtures.
    python - << 'PYEOF'
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import jax.numpy as jnp
from dataclasses import replace
from mxtpu import telemetry as tm
from mxtpu.models import llama
from mxtpu.serve import ServeEngine
from mxtpu.serve.gateway import GatewayClient
from mxtpu.serve.fleet import FleetGateway, ModelSpec

cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense")
pa = llama.init_params(cfg, jax.random.PRNGKey(0))
pb = llama.init_params(cfg, jax.random.PRNGKey(1))

def fac(p0):
    return lambda params=p0: ServeEngine(cfg, params, max_slots=2,
                                         max_len=32, min_bucket=4)

peer_reg = tm.MetricsRegistry()
peer_reg.counter("ci_fleet_peer_total", "federation probe").inc(3)
peer = tm.RegistryServer(port=0, registry=peer_reg, process="worker0")
fleet = FleetGateway(
    [ModelSpec("alpha", fac(pa)), ModelSpec("beta", fac(pb))],
    supervise=False, federate=[("127.0.0.1", peer.port)])
port = fleet.start_http(port=0)
cli = GatewayClient("127.0.0.1", port)
rng = np.random.default_rng(13)
prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 5)]

def ref(params, seed):
    out = llama.generate(cfg, params,
                         jnp.asarray(prompt, jnp.int32)[None], 4,
                         rng=jax.random.PRNGKey(seed))
    return [int(t) for t in np.asarray(out)[0, 5:]]

ra = cli.generate(prompt, 4, seed=2, model="alpha")
rb = cli.generate(prompt, 4, seed=2, model="beta")
for rec, p in ((ra, pa), (rb, pb)):
    assert rec["status"] == 200 and rec["reason"] == "complete", rec
    assert rec["tokens"] == ref(p, 2), rec
assert (ra["model"], ra["version"]) == ("alpha", "v0"), ra
assert ra["tokens"] != rb["tokens"], "two models, one output"

# live hot-swap: alpha takes beta's weights, nothing dropped, the
# next response carries the new build label and its tokens
swap = fleet.hot_swap("alpha", params=pb)
assert swap["version"] == "v1" and swap["swapped"] == 1, swap
r2 = cli.generate(prompt, 4, seed=2, model="alpha")
assert r2["status"] == 200 and r2["version"] == "v1", r2
assert r2["tokens"] == ref(pb, 2), r2

status, prom = cli.get_text("/metrics")
assert status == 200
parsed = tm.parse_prometheus(prom)          # strict grammar
s = parsed["samples"]
assert s[("mxtpu_gateway_requests_total",
          (("code", "accepted"), ("model", "alpha")))] >= 2
assert s[("mxtpu_fleet_swap_total", (("model", "alpha"),))] == 1
assert s[("mxtpu_ci_fleet_peer_total",
          (("process", "worker0"),))] == 3, "federation broken"
status, state = cli.get_json("/state")
assert status == 200 and set(state["models"]) == {"alpha", "beta"}
assert state["models"]["alpha"]["version"] == "v1", state
fleet.close()
peer.close()
print(f"fleet_smoke: OK (2 models bit-identical, hot-swap to "
      f"{swap['version']}, {len(prom.splitlines())} federated "
      f"metric lines)")
PYEOF
}

chaos_serve() {
    # serving-tier fault tolerance (docs/robustness.md §serving): the
    # seeded gateway-chaos suite — replica kill under a Poisson client
    # stream, stall detection, deterministic re-dispatch bit-identity,
    # severed/corrupted KV channel self-healing, prefill-worker
    # respawn, circuit-breaker fallback — in a fresh pytest process,
    # then tools/flakiness_checker.py x3 over the file to prove the
    # chaos plans are deterministic (a flaky fault-tolerance test is
    # worse than none — the PR 2 discipline, applied to serving).
    python -m pytest tests/test_serve_chaos.py -x -q "$@"
    python tools/flakiness_checker.py tests/test_serve_chaos.py -n 3
}

chaos_train() {
    # elastic-training fault tolerance (docs/robustness.md §"Elastic
    # training"): the seeded train-chaos suite — host kill + resume
    # bit-identity on both train paths, dp=2 -> dp=1 cross-mesh restore
    # with the data-position journal proven (no batch replayed or
    # skipped), host loss with elastic shrink, straggler eviction,
    # SIGTERM final-save, NaN-batch nonfinite skip, loss-spike rollback
    # with a bounded budget, torn checkpoints/journals — in a fresh
    # pytest process, then tools/flakiness_checker.py x3 to prove the
    # chaos plans are deterministic.
    python -m pytest tests/test_elastic.py -x -q "$@"
    python tools/flakiness_checker.py tests/test_elastic.py -n 3
}

flywheel_smoke() {
    # continuous train->serve deployment (docs/robustness.md
    # §"Continuous deployment"): the full flywheel suite — the
    # manifest-committed publish seam, the controller state machine,
    # train/serve chip lending, and BOTH end-to-end cycles
    # (publish->canary->promote, publish->canary->breach->rollback)
    # under concurrent train + serve chaos — in a fresh pytest
    # process, then tools/flakiness_checker.py x3 to prove the chaos
    # is seeded, then the service path with no pytest fixtures: a
    # real elastic trainer publishes into a live two-replica fleet,
    # one candidate promotes on a clean hold window, the next burns
    # its canary SLO split and auto-rolls-back to last-good, every
    # response bit-identical to the build version that served it.
    python -m pytest tests/test_flywheel.py -x -q "$@"
    python tools/flakiness_checker.py tests/test_flywheel.py -n 3
    python - << 'PYEOF'
import os, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import jax.numpy as jnp
import optax
from dataclasses import replace
from mxtpu import telemetry as tm
from mxtpu.checkpoint import CheckpointManager
from mxtpu.models import llama
from mxtpu.parallel import (ElasticTrainer, JournaledData, P,
                            ShardingRules, StepProgram, create_mesh,
                            init_state, make_train_step)
from mxtpu.serve import ServeEngine
from mxtpu.serve.fleet import (FleetGateway, FlywheelController,
                               ModelSpec)

cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense")
pa = llama.init_params(cfg, jax.random.PRNGKey(0))
pb = llama.init_params(cfg, jax.random.PRNGKey(1))

def fac(p0):
    return lambda params=p0: ServeEngine(cfg, params, max_slots=2,
                                         max_len=32, min_bucket=4)

prompt = [2, 4, 6, 8]
def ref(params, seed):
    out = llama.generate(cfg, params,
                         jnp.asarray(prompt, jnp.int32)[None], 4,
                         rng=jax.random.PRNGKey(seed))
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]
refs = {"v0": ref(pa, 3), "v1": ref(pb, 3), "v2": ref(pa, 3)}

# a real trainer publishes manifest-committed candidates on a cadence
def batch_fn(i):
    rng = np.random.default_rng(1000 + i)
    return (jnp.asarray(rng.standard_normal((8, 3)).astype(np.float32)),
            jnp.asarray(rng.standard_normal((8, 2)).astype(np.float32)))

def program(world):
    mesh = create_mesh(dp=1, devices=jax.devices()[:1])
    rules = ShardingRules([(r".*", P())])
    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)
    tx = optax.adam(1e-2)
    state = init_state({"w": jnp.ones((3, 2), jnp.float32)}, tx,
                       mesh, rules)
    return StepProgram(make_train_step(loss_fn, tx, mesh, rules),
                       state)

d = tempfile.mkdtemp(prefix="flywheel_ci_")
mgr = CheckpointManager(d, async_save=False)
tr = ElasticTrainer(program, JournaledData(batch_fn), mgr,
                    save_every=2, spike_window=0, publish_every=2)
stats = tr.run(4)
assert stats["published"] == 2, stats

fleet = FleetGateway([ModelSpec("m", fac(pa), replicas=2,
                                slo={"ttft_ms": 60000.0})],
                     supervise=False)
cand = [pb, pa]
fly = FlywheelController(
    fleet, "m", d,
    load_candidate=lambda ptr: (mgr.restore(int(ptr["step"])),
                                cand.pop(0))[1],
    canary_fraction=0.5, hold_ticks=1, burn_high=1.0,
    max_rollbacks=2, poll_s=0.05, slo={"ttft_ms": 10.0},
    anomaly_budget=10_000)

# cycle 1: the latest published candidate canaries into 1 of 2
# replicas, holds a clean window under live traffic, promotes
fly.tick()
assert fly.phase == "canary", fly.describe()
assert fly.canary["version"] == "v1" and fly.canary["canaries"] == 1
h = fleet.submit_dict({"model": "m", "prompt": prompt,
                       "max_new_tokens": 4, "seed": 3})
toks = list(h.result(timeout=180))
assert toks == refs[h.version], (h.version, toks)
fly.tick()
assert fly.phase == "idle" and fleet.pool("m").version == "v1", \
    fly.describe()

# cycle 2: the next candidate burns its canary SLO split and the
# controller auto-rolls-back to last-good, within budget
mgr.publish(2)                      # re-publish: seq advances
fly.tick()
assert fly.phase == "canary" and fly.canary["version"] == "v2"
gw = fleet.gateway("m")
for _ in range(5):
    gw.version_ttft("v2").observe(5000.0)
fly.tick()
assert fly.phase == "idle" and fly.rollbacks == 1 and not fly.halted
assert fleet.pool("m").version == "v1", fleet.state()["models"]["m"]
assert tm.registry().value("fleet_rollback_total", model="m",
                           reason="slo_burn") == 1
for r in fleet.pool("m").replicas():
    if r.version != "v1":
        fleet.pool("m").drain_replica(r)
h = fleet.submit_dict({"model": "m", "prompt": prompt,
                       "max_new_tokens": 4, "seed": 3})
assert list(h.result(timeout=180)) == refs["v1"]
assert h.version == "v1", h.version
mgr.close()
fleet.close()
print(f"flywheel_smoke: OK ({stats['published']} published, "
      f"promote v0->v1, v2 burned and rolled back to v1, "
      f"responses bit-identical per build)")
PYEOF
}

telemetry_smoke() {
    # the observability layer end to end in a fresh process on the
    # ENABLED-BY-DEFAULT path (docs/observability.md): metrics through
    # real subsystem work, a valid Prometheus text dump, a parseable
    # chrome-trace JSONL stream, a recompile attributed to its cache
    # key, and a readable flight-recorder dump. The full contract is
    # tier-1 in tests/test_telemetry.py; this proves it without pytest.
    python - << 'PYEOF'
import json, os, tempfile
tmp = tempfile.mkdtemp()
trace_path = os.path.join(tmp, "trace.jsonl")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MXTPU_TELEMETRY_TRACE_PATH"] = trace_path
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from mxtpu import telemetry as tm

assert tm.enabled(), "telemetry must be on by default"
tm.install_compile_listener()
with tm.span("smoke.outer", stage="ci"):
    f = tm.watch(jax.jit(lambda x: x * 2), "smoke_fn", expected=1)
    f(jnp.ones((4,), jnp.float32))
    f(jnp.ones((4,), jnp.float32))       # cached
    f(jnp.ones((8,), jnp.float32))       # cache-key bust -> recompile
assert tm.registry().value("jax_compile_total") >= 2
assert tm.registry().value("recompile_total", fn="smoke_fn") == 1
assert "8" in f.compiles[-1], f.compiles

prom = tm.prometheus()
assert "# TYPE mxtpu_jax_compile_total counter" in prom, prom[:400]
for line in prom.splitlines():
    assert line.startswith("#") or " " in line, line

with open(trace_path) as fh:
    events = [json.loads(l) for l in fh]
assert any(e["name"] == "smoke.outer" for e in events), events

dump = tm.flight().dump(os.path.join(tmp, "flight.jsonl"))
recs = [json.loads(l) for l in open(dump)]
assert any(r["kind"] == "recompile" for r in recs), recs
print(f"telemetry_smoke: OK ({len(events)} trace events, "
      f"{len(recs)} flight records, prometheus "
      f"{len(prom.splitlines())} lines)")
PYEOF
    # ISSUE 8 end to end, across REAL process boundaries: a
    # fresh-process disagg gateway federating two fresh-process
    # metrics peers serves one traced HTTP request; the driver then
    # (a) stitches the gateway process's per-process trace stream
    # into a chrome-trace timeline via the diagnose CLI and (b)
    # validates the federated /metrics scrape — >= 3 `process` labels
    # under strict Prometheus grammar.
    python - << 'PYEOF'
import json, os, subprocess, sys, tempfile, time
tmp = tempfile.mkdtemp()
# the child scripts live under the tmp dir: the repo root must reach
# their sys.path explicitly (a stdin heredoc gets cwd for free)
env = dict(os.environ, JAX_PLATFORMS="cpu",
           MXTPU_TELEMETRY_TRACE_DIR=tmp,
           PYTHONPATH=os.getcwd() + os.pathsep
           + os.environ.get("PYTHONPATH", ""))

peer_src = r"""
import sys, time
from mxtpu import telemetry as tm
role = sys.argv[1]
tm.counter("ci_peer_total", "per-process federation probe").inc(2)
srv = tm.RegistryServer(port=0, process=role)
print(srv.port, flush=True)
time.sleep(600)
"""
gw_src = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from mxtpu import telemetry as tm
from mxtpu.models import llama
from mxtpu.serve.gateway import DisaggBackend, Gateway
tm.set_process_role("gateway")
tm.counter("ci_peer_total", "per-process federation probe").inc(1)
peers = [("127.0.0.1", int(p)) for p in sys.argv[1:]]
cfg = replace(llama.CONFIGS["tiny"], dtype=jnp.float32, remat=False,
              attn_impl="dense")
params = llama.init_params(cfg, jax.random.PRNGKey(0))
be = DisaggBackend(cfg, params, n_prefill=1, n_decode=1, max_slots=2,
                   max_len=32, min_bucket=4)
gw = Gateway(backend=be, queue_max=16, federate=peers)
print(gw.start_http(port=0), flush=True)
import time; time.sleep(600)
"""
for name, src in (("peer.py", peer_src), ("gw.py", gw_src)):
    open(os.path.join(tmp, name), "w").write(src)

procs = []
try:
    ports = []
    for role in ("prefill_host", "kvstore"):
        p = subprocess.Popen(
            [sys.executable, os.path.join(tmp, "peer.py"), role],
            stdout=subprocess.PIPE, text=True, env=env)
        procs.append(p)
        ports.append(int(p.stdout.readline()))
    gwp = subprocess.Popen(
        [sys.executable, os.path.join(tmp, "gw.py")]
        + [str(p) for p in ports],
        stdout=subprocess.PIPE, text=True, env=env)
    procs.append(gwp)
    gw_port = int(gwp.stdout.readline())

    from mxtpu.serve.gateway import GatewayClient
    from mxtpu.telemetry import parse_prometheus
    cli = GatewayClient("127.0.0.1", gw_port, timeout=300.0)
    rec = cli.generate(list(range(1, 6)), 4, seed=3, temperature=0.8)
    assert rec["status"] == 200 and rec["reason"] == "complete", rec
    assert len(rec["tokens"]) == 4 and rec["trace_id"], rec

    # (a) stitched timeline through the CLI, valid chrome-trace JSON
    out = os.path.join(tmp, "timeline.json")
    r = subprocess.run(
        [sys.executable, "tools/diagnose.py", "timeline",
         rec["trace_id"], "--dir", tmp, "--out", out],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    tl = json.load(open(out))
    names = {e["name"] for e in tl}
    assert {"gateway.submit", "gateway.prefill", "serve.seat",
            "serve.done"} <= names, names
    assert all(e["ph"] == "M" or ("ts" in e and "pid" in e)
               for e in tl)

    # (b) federated scrape: strict grammar, >= 3 process labels,
    # aggregate == sum for the probe counter planted in every process
    status, text = cli.get_text("/metrics")
    assert status == 200
    parsed = parse_prometheus(text)
    s = parsed["samples"]
    procs_seen = {dict(k[1]).get("process") for k in s
                  if dict(k[1]).get("process")}
    assert {"gateway", "prefill_host", "kvstore"} <= procs_seen, \
        procs_seen
    total = s[("mxtpu_ci_peer_total", ())]
    parts = [s[("mxtpu_ci_peer_total", (("process", p),))]
             for p in ("gateway", "prefill_host", "kvstore")]
    assert total == sum(parts) == 5.0, (total, parts)
    print(f"telemetry_smoke (distributed): OK — timeline "
          f"{len(tl)} events, federated scrape across "
          f"{len(procs_seen)} processes")
finally:
    for p in procs:
        p.kill()
PYEOF
    # ISSUE 13 end to end in a fresh process: one train step and one
    # serve request publish cost-model roofline gauges (program
    # FLOPs, live MFU/MBU, KV reserved-vs-live, HBM headroom) on a
    # SINGLE /metrics scrape, and `tools/diagnose.py perf` renders
    # the roofline attribution table from that same scrape file.
    python - << 'PYEOF'
import os, subprocess, sys, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax
from mxtpu import telemetry as tm
from mxtpu.models import llama
from mxtpu.parallel import mesh as pmesh, step as pstep
from mxtpu.serve import Request, ServeEngine

cfg = llama.LlamaConfig(
    vocab_size=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2,
    hidden_dim=32, max_seq_len=16)
mesh = pmesh.create_mesh(dp=-1)
rules = llama.sharding_rules(cfg)
params = llama.init_params(cfg, jax.random.PRNGKey(0))
tx = optax.adamw(1e-3)
state = pstep.init_state(params, tx, mesh, rules)
step = pstep.make_train_step(llama.loss_fn(cfg), tx, mesh, rules)
batch = {"tokens": np.zeros((jax.device_count(), 16), np.int32)}
for _ in range(3):
    state, loss = step(state, batch)
jax.block_until_ready(loss)

scfg = llama.LlamaConfig(
    vocab_size=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2,
    hidden_dim=32, max_seq_len=32)
sparams = llama.init_params(scfg, jax.random.PRNGKey(1))
eng = ServeEngine(scfg, sparams, max_slots=2, max_len=32,
                  min_bucket=4)
eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
eng.run()

prom = tm.prometheus()
s = tm.parse_prometheus(prom)["samples"]
def val(name, **labels):
    return s.get((name, tuple(sorted(labels.items()))), 0.0)
assert val("mxtpu_program_flops", program="train_step") > 0, \
    "train_step missing from cost catalog"
assert val("mxtpu_program_flops", program="serve_decode") > 0, \
    "serve_decode missing from cost catalog"
assert any(k[0] == "mxtpu_mfu" for k in s), "no live MFU gauge"
assert any(k[0] == "mxtpu_hbm_bw_util" for k in s), "no MBU gauge"
assert val("mxtpu_serve_kv_reserved_bytes",
           engine=eng.engine_id) > 0
assert ("mxtpu_hbm_headroom_bytes", ()) in s, "no HBM headroom"
assert val("mxtpu_hbm_ledger_bytes", category="params") > 0
assert val("mxtpu_hbm_ledger_bytes", category="kv_page_pool") > 0

scrape = os.path.join(tempfile.mkdtemp(), "scrape.txt")
open(scrape, "w").write(prom)
r = subprocess.run(
    [sys.executable, "tools/diagnose.py", "perf", scrape],
    capture_output=True, text=True, timeout=120)
assert r.returncode == 0, r.stdout + r.stderr
assert "train_step" in r.stdout and "serve_decode" in r.stdout, \
    r.stdout
n_prog = sum(1 for k in s if k[0] == "mxtpu_program_flops")
print(f"telemetry_smoke (perfscope): OK — {n_prog} cataloged "
      f"programs, roofline table rendered from one scrape")
print(r.stdout)
PYEOF
}

jax_platform() {
    # The platform jax runs on here ("tpu", "cpu", ...), asked of a
    # short-lived child that lets the chip go when it exits. A chip
    # belongs to one process at a time, so a gate's own process never
    # imports jax: the measuring child it starts is the one that opens
    # the chip. A probe that fails fails the stage (set -e).
    python -c "import jax; print(jax.devices()[0].platform)" | tail -n 1
}

opperf_gate() {
    # The 329/329 coverage claim must be RECORDED, and per-op latency
    # GATED against a baseline taken on the machine that measures
    # (upstream benchmark/opperf was a perf harness, not a checklist).
    # With a chip and a baseline for it the sweep runs on the chip and
    # compares (tolerance 2.5x on ops with >= 50 ms compute portion,
    # violators re-timed twice — see the cmd flags below); with a chip
    # and no baseline the sweep still runs there but gates coverage
    # only and says so; CPU-only boxes gate coverage alone — CPU
    # latencies at --iters 2 are noise. Take the baseline with
    # `ci/runtime_functions.sh opperf_baseline`.
    local platform
    platform="$(jax_platform)"
    python - "$platform" << 'PYEOF'
import json, os, re, subprocess, sys
on_chip = sys.argv[1] != "cpu"
baseline = "benchmark/opperf/baseline_tpu.json"
cmd = [sys.executable, "benchmark/opperf/opperf.py", "--all",
       "--iters", "2", "--json", "benchmark/opperf/coverage_latest.json"]
env = dict(os.environ)
gated = on_chip and os.path.exists(baseline)
if gated:
    # only ops with a >=50 ms compute portion are gateable, at 2.5x;
    # the mechanics are the benchmark's business (ROADMAP S1/D3)
    cmd += ["--compare", baseline, "--min-ms", "50",
            "--tolerance", "2.5"]
elif not on_chip:
    env["JAX_PLATFORMS"] = "cpu"
out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                     timeout=3000)
sys.stdout.write(out.stdout[-2000:])
assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
m = re.search(r"covered (\d+)/(\d+) registered ops \((\d+) need",
              out.stdout)
assert m, f"no coverage line in output:\n{out.stdout[-500:]}"
covered, total, misfits = map(int, m.groups())
assert covered == total and misfits == 0, \
    f"opperf coverage regressed: {covered}/{total}, {misfits} misfits"
n_json = len(json.load(open("benchmark/opperf/coverage_latest.json")))
assert n_json == total, (n_json, total)
mode = ("chip latency gate + coverage" if gated else
        "coverage on the chip; latency: no baseline — not gated"
        if on_chip else "coverage only (no chip)")
print(f"opperf_gate: OK ({covered}/{total} ops, {mode})")
PYEOF
}

# back-compat name (round-4 CI docs referenced opperf_coverage)
opperf_coverage() { opperf_gate "$@"; }

bench_gate() {
    # Whole-model step-time/MFU gate — the model-level analogue of
    # opperf_gate. With a chip and a baseline taken on it
    # (benchmark/baseline_models.json; tolerance band in the file,
    # violators re-timed once) the flagship configs are re-measured and
    # compared; with a chip and no baseline the stage says "no baseline
    # — not gated" and passes (ROADMAP S1 replaces this gate). On
    # CPU-only boxes chip latencies are meaningless, so the gate
    # instead runs a live mini-gate on the CPU-safe smoke config
    # against a freshly-measured self-baseline, which proves the
    # measure+compare plumbing end to end (MXTPU_BENCH_INJECT seeds a
    # regression; the exact 10%-regression logic contract is
    # tier-1-gated in tests/test_bench_gate.py).
    local platform
    platform="$(jax_platform)"
    python - "$platform" << 'PYEOF'
import json, os, subprocess, sys, tempfile
on_chip = sys.argv[1] != "cpu"
baseline = "benchmark/baseline_models.json"
env = dict(os.environ)
if on_chip and not os.path.exists(baseline):
    print("bench_gate: OK (chip, no baseline — not gated)")
    sys.exit(0)
if on_chip:
    cmd = [sys.executable, "bench.py", "gate", "--baseline", baseline]
else:
    env["JAX_PLATFORMS"] = "cpu"
    tmp = os.path.join(tempfile.mkdtemp(), "self_base.json")
    mk = subprocess.run(
        [sys.executable, "bench.py", "gate", "--configs", "smoke_llama",
         "--baseline", tmp, "--update"],
        capture_output=True, text=True, timeout=1200,
        env={k: v for k, v in env.items()
             if k != "MXTPU_BENCH_INJECT"})
    assert mk.returncode == 0, mk.stderr[-2000:] + mk.stdout[-500:]
    cmd = [sys.executable, "bench.py", "gate", "--baseline", tmp,
           "--tolerance", "2.0", "--configs", "smoke_llama"]
out = subprocess.run(cmd, capture_output=True, text=True,
                     timeout=3600, env=env)
sys.stdout.write(out.stdout[-2000:])
if out.returncode != 0:
    sys.stderr.write(out.stderr[-1000:])
    sys.exit(1)
mode = "chip step-time gate" if on_chip else \
    "smoke plumbing (no chip)"
print(f"bench_gate: OK ({mode})")
PYEOF
}

bench_gate_baseline() {
    # take the whole-model baseline on the machine that will be gated
    # (a chip box; one process, so it holds the chip alone), then
    # commit the json — the sibling of opperf_baseline
    python bench.py gate --update \
        --configs resnet50,resnet50_s2d,bert_base,llama_509m,llama_509m_decode,llama_509m_decode_int8,llama_509m_serve,llama_509m_gateway
    echo "bench_gate_baseline: wrote benchmark/baseline_models.json"
}

opperf_baseline() {
    # take the chip baseline on the machine that will be gated, then
    # commit the json
    python benchmark/opperf/opperf.py --all --iters 2 \
        --json benchmark/opperf/baseline_tpu.json
    echo "opperf_baseline: wrote benchmark/opperf/baseline_tpu.json"
}

ci_all() {
    sanity_check
    mxlint
    unittest_cpu_mesh
    fault_tolerance
    multichip_dryrun
    bench_smoke
    serve_smoke
    paged_kv_smoke
    paged_kv_slow
    spec_smoke
    spec_decode_slow
    gateway_smoke
    fleet_smoke
    chaos_serve
    chaos_train
    flywheel_smoke
    lockcheck_smoke
    telemetry_smoke
    opperf_coverage
    bench_gate
}

ci_fast() {
    # the default inner loop (VERDICT r5 #7): lint + the not-slow unit
    # tier + the bench-path smoke — minutes, not the 52-minute ci_all.
    # Run ci_all (full suite, dist/chaos/dryrun/opperf) before a
    # snapshot or when touching distributed/CI surfaces.
    sanity_check
    mxlint
    unittest_fast
    bench_smoke
    serve_smoke
    paged_kv_smoke
    spec_smoke
    gateway_smoke
    fleet_smoke
    chaos_serve
    chaos_train
    flywheel_smoke
    lockcheck_smoke
    telemetry_smoke
}

# no-argument invocation runs the fast inner loop, so the cheap,
# always-appropriate check is also the default one (VERDICT r5 #7: an
# untested snapshot happened because the fast path wasn't the default)
if [ "$#" -eq 0 ]; then
    set -- ci_fast
fi

"$@"
