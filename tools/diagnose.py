#!/usr/bin/env python
"""Environment + runtime diagnostics (reference ``tools/diagnose.py``).

Beyond the static environment report, prints the LIVE telemetry
summary table and the flight-recorder tail — importable as
``from tools.diagnose import report; report()`` inside a running job,
where "what was this job doing" is answered by the last N recorded
events. Standalone invocation also tails any on-disk flight dump left
by a preempted/crashed process (``MXTPU_TELEMETRY_FLIGHT_PATH``).

``python tools/diagnose.py timeline <rid-or-trace-id>`` stitches the
PER-PROCESS trace JSONL files of a distributed serving run
(``MXTPU_TELEMETRY_TRACE_DIR``) into ONE chrome://tracing-loadable
JSON file for that request — front door, prefill worker, every decode
replica it touched, and any crash re-dispatch seam, on one timeline.

``python tools/diagnose.py perf [source]`` renders the perfscope
roofline attribution table (program, cost-model FLOPs/bytes,
compute- vs memory-bound class, live MFU, share of wall time) from
one /metrics scrape — this process, a gateway address, or a saved
scrape file.

``python tools/diagnose.py fleet <host:port>`` renders a running
fleet gateway's per-model pool table (replicas, build version,
priority mix, SLO burn, chips, last arbiter decision) from one
/state + /metrics scrape.

``python tools/diagnose.py lint [report]`` renders an mxlint report —
the SARIF file CI's mxlint stage writes (default
``build/mxlint_deep.sarif``) or ``--json`` output — as a per-rule
table: rule, finding count, first site, description.
"""
import glob as _glob
import json
import os
import platform
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def report(flight_tail: int = 20):
    """The runtime half: telemetry summary + flight-recorder tail for
    THIS process."""
    from mxtpu import telemetry
    print("----------Telemetry Summary----------")
    print(telemetry.summary())
    print(f"----------Flight Recorder (last {flight_tail})----------")
    print(telemetry.flight().format_tail(flight_tail))


def gateway_state(addr: str = ""):
    """Live serving-gateway topology: replica/queue state scraped from
    a running gateway's GET /state (``MXTPU_GATEWAY_ADDR=host:port``,
    or pass the address). In-process gateway metrics already appear in
    report()'s telemetry summary; this reaches a gateway in ANOTHER
    process — the deployment case."""
    addr = addr or os.environ.get("MXTPU_GATEWAY_ADDR", "")
    if not addr:
        return
    host, _, port = addr.partition(":")
    print(f"----------Gateway state ({addr})----------")
    try:
        from mxtpu.serve.gateway import GatewayClient
        status, state = GatewayClient(host, int(port or 9300),
                                      timeout=5.0).get_json("/state")
    except Exception as e:
        print(f"unreachable: {e!r}")
        return
    if status != 200:
        print(f"HTTP {status}: {state}")
        return
    health = state.get("health") or {}
    status = health.get("status", "?")
    print(f"replicas={state['n_replicas']}  queued={state['queued']}"
          f"/{state['queue_max']}  active={state['active']}"
          f"/{state['slots']} slots  health={status}"
          + (f" (shed tier {health['tier']})"
             if health.get("tier") else ""))
    for r in state.get("replicas", []):
        role = r.get("role", "engine")
        up = ("up" if r.get("healthy", r.get("alive"))
              else ("DEAD" if r.get("failed") else "down"))
        line = (f"  {r['name']:<10} {role:<8} {up:<5} "
                f"queued={r['queued']} active={r['active']}"
                f"/{r['slots']}")
        if r.get("steps") is not None:
            line += f" steps={r['steps']}"
        if r.get("error"):
            line += f" error={r['error']}"
        print(line)
    slo = health.get("slo")
    if slo:
        for name, v in sorted((slo.get("slos") or {}).items()):
            burn = v.get("burn")
            print(f"slo {name}: p99={v.get('p99_ms')}ms "
                  f"target={v.get('target_ms')}ms "
                  f"burn={'n/a' if burn is None else round(burn, 2)}"
                  + (" BREACHED" if burn is not None and
                     burn > slo.get("burn_threshold", 1.0) else ""))
    breaker = state.get("breaker")
    if breaker:
        print(f"breaker: {breaker['state']} "
              f"(failures={breaker['failures']}"
              f"/{breaker['threshold']}, trips={breaker['trips']})")
    sup = state.get("supervisor")
    if sup:
        print(f"supervisor: restarts={sup['restarts']}"
              f"/{sup['max_restarts']} "
              f"pending_spawns={sup['pending_spawns']}")
        for h in sup.get("history", []):
            print(f"  restart {h['replica']} reason={h['reason']}"
                  + (f" error={h['error']}" if h.get("error") else ""))
    scaler = state.get("autoscaler")
    if scaler:
        print(f"autoscaler: replicas={scaler['replicas']} in "
              f"[{scaler['min']}, {scaler['max']}] "
              f"target_p99={scaler['target_p99_ms']}ms "
              f"last_p99={scaler['last_p99_ms']}")
        for d in scaler.get("decisions", []):
            print(f"  scale {d['direction']} {d['from']}->{d['to']} "
                  f"pressure={d['pressure']} p99={d['p99_ms']}")


def kv_state(addr: str = ""):
    """``python tools/diagnose.py kv <host:port>`` — the paged-KV
    view of a running gateway, from ONE GET /state scrape: page-pool
    occupancy, shared pages, prefix-cache hit rate, speculative-decode
    acceptance, prefix-affinity routing counts, and the top shared
    prefixes, fleet-aggregated and then per decode replica."""
    addr = addr or os.environ.get("MXTPU_GATEWAY_ADDR", "")
    if not addr:
        return False
    host, _, port = addr.partition(":")
    print(f"----------KV cache ({addr})----------")
    try:
        from mxtpu.serve.gateway import GatewayClient
        status, state = GatewayClient(host, int(port or 9300),
                                      timeout=5.0).get_json("/state")
    except Exception as e:
        print(f"unreachable: {e!r}")
        return False
    if status != 200:
        print(f"HTTP {status}: {state}")
        return False
    kv = state.get("kv_cache") or {}
    occ = kv.get("occupancy", 0.0)
    print(f"reserved={kv.get('reserved_bytes', 0):,}B "
          f"live={kv.get('live_bytes', 0):,}B "
          f"occupancy={occ:.3f} "
          f"active={kv.get('active', 0)}/{kv.get('slots', 0)} slots")
    total = kv.get("pages_total", 0)
    used = kv.get("pages_used", 0)
    hits = kv.get("prefix_hits", 0)
    misses = kv.get("prefix_misses", 0)
    rate = kv.get("prefix_hit_rate",
                  hits / (hits + misses) if hits + misses else 0.0)
    print(f"pages: {used}/{total} used "
          f"({kv.get('pages_free', 0)} free, "
          f"{kv.get('pages_shared', 0)} shared) "
          f"cow_forks={kv.get('cow_forks', 0)}")
    print(f"prefix cache: hits={hits} misses={misses} "
          f"hit_rate={rate:.3f}")
    if kv.get("spec_proposed", 0):
        print(f"speculative: proposed={kv.get('spec_proposed', 0)} "
              f"accepted={kv.get('spec_accepted', 0)} "
              f"accept_rate={kv.get('spec_accept_rate', 0.0):.3f}")
    aff = state.get("prefix_affinity") or {}
    if aff.get("hit", 0) or aff.get("miss", 0):
        tot = aff.get("hit", 0) + aff.get("miss", 0)
        print(f"prefix affinity: hits={aff.get('hit', 0)} "
              f"misses={aff.get('miss', 0)} "
              f"hit_rate={aff.get('hit', 0) / tot:.3f}")
    for p in kv.get("top_prefixes", []):
        print(f"  prefix len={p.get('n_tokens')} "
              f"hits={p.get('hits')} pages={p.get('pages')} "
              f"head={p.get('head')}")
    for r in state.get("replicas", []):
        rkv = r.get("kv_cache") if isinstance(r, dict) else None
        if not rkv:
            continue
        spec = (f"accept={rkv.get('spec_accept_rate', 0.0):.2f} "
                if rkv.get("speculate_k") else "")
        print(f"  {r.get('name', '?'):<10} "
              f"pages={rkv.get('pages_used', 0)}"
              f"/{rkv.get('pages_total', 0)} "
              f"shared={rkv.get('pages_shared', 0)} "
              f"hits={rkv.get('prefix_hits', 0)} "
              f"misses={rkv.get('prefix_misses', 0)} "
              f"cow={rkv.get('cow_forks', 0)} " + spec +
              f"entries={rkv.get('prefix_entries', 0)}")
    return True


def fleet_state(addr: str = ""):
    """``python tools/diagnose.py fleet <host:port>`` — the fleet
    control plane at a glance, from ONE /state + ONE /metrics scrape
    of a running :class:`~mxtpu.serve.fleet.FleetGateway`: a per-model
    pool table (replicas vs bounds, build version, queue, priority
    mix, SLO burn, chips, last arbiter decision), the arbiter's chip
    ledger, and which ``process=`` labels the federated scrape joins
    (``MXTPU_GATEWAY_ADDR=host:port``, or pass the address)."""
    addr = addr or os.environ.get("MXTPU_GATEWAY_ADDR", "")
    if not addr:
        return False
    host, _, port = addr.partition(":")
    print(f"----------Fleet state ({addr})----------")
    try:
        from mxtpu.serve.gateway import GatewayClient
        cli = GatewayClient(host, int(port or 9300), timeout=5.0)
        status, state = cli.get_json("/state")
        mstatus, text = cli.get_text("/metrics")
    except Exception as e:
        print(f"unreachable: {e!r}")
        return False
    if status != 200 or mstatus != 200:
        print(f"HTTP {status}/{mstatus}: {state}")
        return False
    models = state.get("models")
    if not isinstance(models, dict):
        print("not a fleet gateway (no per-model state); try "
              "`diagnose.py gateway` semantics via the default report")
        return False
    from mxtpu import telemetry
    try:
        samples = telemetry.parse_prometheus(text)["samples"]
    except ValueError as e:
        print(f"malformed /metrics scrape: {e}")
        return False
    # burn per model: the AGGREGATE series (no process label) — the
    # federated scrape also carries per-process copies, which the
    # process list below accounts for
    burn, chips = {}, {}
    for (name, labels), value in samples.items():
        d = dict(labels)
        if "process" in d:
            continue
        if name == "mxtpu_gateway_slo_burn_rate" and "model" in d:
            burn[d["model"]] = max(burn.get(d["model"], 0.0), value)
        elif name == "mxtpu_fleet_chips_in_use" and "model" in d:
            chips[d["model"]] = int(value)
    lines = [("model", "ver", "replicas", "queue", "active",
              "priority mix", "burn", "chips", "last decision")]
    for name, st in sorted(models.items()):
        mix = st.get("priority_mix") or {}
        mix_s = "/".join(str(mix.get(p, 0)) for p in
                         ("interactive", "batch", "offline"))
        d = st.get("arbiter_last")
        last = "-" if not d else (
            f"{d['direction']} {d['from']}->{d['to']} "
            f"({d['reason']})")
        b = burn.get(name)
        lines.append((
            name, str(st.get("version", "-")),
            f"{st['n_replicas']} [{st.get('min_replicas', '?')},"
            f"{st.get('max_replicas', '?')}]",
            f"{st['queued']}/{st['queue_max']}",
            f"{st['active']}/{st['slots']}", mix_s,
            "-" if b is None else f"{b:.2f}",
            str(chips.get(name, "-")), last))
    widths = [max(len(row[i]) for row in lines)
              for i in range(len(lines[0]))]
    for row in lines:
        print("  ".join(c.ljust(w)
                        for c, w in zip(row, widths)).rstrip())
    # per-model degraded causes from /healthz (breaker open, supervisor
    # exhausted, SLO burn, active rollback, ...) — the aggregate view
    # the fleet health endpoint computes, not re-derived here
    try:
        hstatus, health = cli.get_json("/healthz")
    except Exception:
        hstatus, health = 0, {}
    if hstatus in (200, 503) and isinstance(health, dict):
        degraded = health.get("degraded") or []
        if degraded:
            print(f"degraded: {', '.join(sorted(degraded))}")
            for name in sorted(degraded):
                h = (health.get("models") or {}).get(name) or {}
                causes = h.get("causes") or []
                print(f"  {name}: {', '.join(causes) or '(unknown)'}")
        else:
            print("degraded: (none)")
    arb = state.get("arbiter")
    if arb:
        print(f"arbiter: budget={arb['budget']} free={arb['free']} "
              f"cooldown={arb['cooldown_s']}s")
        for d in arb.get("decisions", []):
            print(f"  {d['model']}: {d['direction']} "
                  f"{d['from']}->{d['to']} reason={d['reason']} "
                  f"pressure={d['pressure']} burn={d['burn']}")
    print(f"affinity sessions: {state.get('affinity_sessions', 0)}")
    procs = sorted({dict(lab).get("process")
                    for (_, lab) in samples
                    if dict(lab).get("process")})
    print(f"federated processes: {', '.join(procs) or '(local only)'}")
    return True


def flywheel_state(addr: str = ""):
    """``python tools/diagnose.py flywheel <host:port>`` — the
    continuous-deployment loop at a glance, from ONE /state + ONE
    /metrics scrape: per attached :class:`FlywheelController` the
    phase (idle/canary/halted), the last candidate seen, the live
    canary split (replicas on the candidate vs pool size), per-version
    SLO burn, the rollback budget, and the last decisions with their
    reasons (``MXTPU_GATEWAY_ADDR=host:port``, or pass the address)."""
    addr = addr or os.environ.get("MXTPU_GATEWAY_ADDR", "")
    if not addr:
        return False
    host, _, port = addr.partition(":")
    print(f"----------Flywheel state ({addr})----------")
    try:
        from mxtpu.serve.gateway import GatewayClient
        cli = GatewayClient(host, int(port or 9300), timeout=5.0)
        status, state = cli.get_json("/state")
        mstatus, text = cli.get_text("/metrics")
    except Exception as e:
        print(f"unreachable: {e!r}")
        return False
    if status != 200 or mstatus != 200:
        print(f"HTTP {status}/{mstatus}: {state}")
        return False
    flys = state.get("flywheel")
    if not isinstance(flys, dict) or not flys:
        print("no flywheel controllers attached "
              "(FleetGateway.attach_flywheel / FlywheelController)")
        return False
    from mxtpu import telemetry
    try:
        samples = telemetry.parse_prometheus(text)["samples"]
    except ValueError as e:
        print(f"malformed /metrics scrape: {e}")
        return False
    # per-(model, version) burn from the scrape — covers builds whose
    # in-process tracker state the /state block no longer carries
    vburn = {}
    for (name, labels), value in samples.items():
        d = dict(labels)
        if "process" in d:
            continue
        if (name == "mxtpu_gateway_slo_burn_rate"
                and "model" in d and "version" in d):
            key = (d["model"], d["version"])
            vburn[key] = max(vburn.get(key, 0.0), value)
    for name, fly in sorted(flys.items()):
        phase = fly.get("phase", "?")
        if fly.get("halted"):
            phase += " HALTED"
        print(f"{name}: phase={phase} seen_seq={fly.get('seen_seq')} "
              f"fraction={fly.get('fraction')} "
              f"hold_ticks={fly.get('hold_ticks')} "
              f"burn_high={fly.get('burn_high')} "
              f"rollbacks={fly.get('rollbacks')}"
              f"/{fly.get('max_rollbacks')}")
        can = fly.get("canary")
        if can:
            print(f"  canary: {can.get('version')} on "
                  f"{can.get('canaries')}/{can.get('of')} replicas "
                  f"(from {can.get('from_version')}, "
                  f"clean_ticks={can.get('clean_ticks')})")
        burns = dict(fly.get("burn") or {})
        for (m, ver), v in vburn.items():
            if m == name and ver not in burns:
                burns[ver] = v
        for ver in sorted(burns):
            b = burns[ver]
            print(f"  burn[{ver}]: "
                  f"{'-' if b is None else format(b, '.3f')}")
        hist = fly.get("history") or []
        if hist:
            print("  decisions:")
        for h in hist:
            extra = " ".join(
                f"{k}={v}" for k, v in sorted(h.items())
                if k not in ("action", "model", "t"))
            print(f"    {h.get('action')}: {extra}")
    return True


def elastic_state(addr: str = ""):
    """Live elastic-training membership: generation, world size, and
    per-host step/heartbeat-age rows scraped from a running
    ``ElasticCoordinator``'s ``("state",)`` op
    (``MXTPU_ELASTIC_COORD_ADDR=host:port``, or pass the address).
    The same numbers ride the Prometheus scrape as
    ``mxtpu_elastic_*``; this is the point-in-time table view."""
    addr = addr or os.environ.get("MXTPU_ELASTIC_COORD_ADDR", "")
    if not addr:
        return None
    host, _, port = addr.partition(":")
    print(f"----------Elastic coordinator ({addr})----------")
    try:
        import socket
        from mxtpu import rpc
        secret = os.environ.get("MXTPU_ELASTIC_SECRET", "").encode()
        with socket.create_connection((host, int(port or 9400)),
                                      timeout=5.0) as s:
            reply = rpc.call(s, ("state",), secret)
    except Exception as e:
        print(f"unreachable: {e!r}")
        return False
    if not (isinstance(reply, tuple) and reply and reply[0] == "ok"):
        print(f"bad reply: {reply!r}")
        return False
    _, gen, target, world, rows = reply
    resizing = "" if gen == target else \
        f"  (RESIZING -> generation {target})"
    print(f"generation={gen}  world={world}{resizing}")
    for h, step, beat_age in rows:
        print(f"  {h:<12} step={step:<8} last_beat={beat_age}s ago")
    return True


def _trace_files(trace_dir=None, paths=None):
    """The trace JSONL inputs: explicit paths, a directory of
    per-process streams, or whatever the env knobs point at."""
    out = list(paths or [])
    d = trace_dir or os.environ.get("MXTPU_TELEMETRY_TRACE_DIR", "")
    if d:
        out += sorted(_glob.glob(os.path.join(d, "*.jsonl")))
    p = os.environ.get("MXTPU_TELEMETRY_TRACE_PATH", "")
    if p and os.path.exists(p):
        out.append(p)
    # stable de-dup
    seen, files = set(), []
    for f in out:
        if f not in seen:
            seen.add(f)
            files.append(f)
    return files


def _load_events(files):
    events = []
    for f in files:
        role = None
        base = os.path.basename(f)
        if base.startswith("mxtpu_trace_"):
            # mxtpu_trace_<role>_<pid>.jsonl — role may itself
            # contain underscores; the pid is the last segment
            parts = base[len("mxtpu_trace_"):-len(".jsonl")] \
                .rsplit("_", 1)
            role = parts[0] or None
        try:
            with open(f) as fh:
                for line in fh:
                    try:
                        evt = json.loads(line)
                    except ValueError:
                        continue          # torn tail line mid-write
                    if role is not None:
                        evt.setdefault("_role", role)
                    events.append(evt)
        except OSError:
            continue
    return events


def timeline(key, trace_dir=None, paths=None, out=None):
    """Stitch the per-process trace streams into one chrome-trace
    JSON file for ONE request.

    ``key``: a trace id (hex) or a gateway request id (the ``rid``
    baggage every context-tagged event carries). Returns ``(path,
    events)`` — ``path`` is the written chrome://tracing-loadable
    array (None when nothing matched), ``events`` the request's
    events sorted by timestamp. The output carries ``process_name``
    metadata per pid, so chrome's process lanes read as the serving
    roles, not bare pids.

    Clock caveat: event timestamps are CLOCK_MONOTONIC (epoch = host
    boot), comparable across PROCESSES on one host but not across
    hosts. Stitching files collected from several hosts still shows
    every hop, but the relative ordering between hosts is
    meaningless — the function detects fully-disjoint per-process
    clock ranges and warns instead of pretending."""
    files = _trace_files(trace_dir, paths)
    events = _load_events(files)
    key_s = str(key).lower()
    trace_ids = {key_s} if any(
        (e.get("args") or {}).get("trace_id") == key_s
        for e in events) else set()
    if not trace_ids:
        try:
            rid = int(key)
        except (TypeError, ValueError):
            rid = None
        if rid is not None:
            trace_ids = {
                (e.get("args") or {}).get("trace_id")
                for e in events
                if (e.get("args") or {}).get("rid") == rid
                and (e.get("args") or {}).get("trace_id")}
    mine = sorted(
        (e for e in events
         if (e.get("args") or {}).get("trace_id") in trace_ids),
        key=lambda e: e.get("ts", 0))
    if not mine:
        print(f"timeline: no events for {key!r} in "
              f"{len(files)} trace file(s)")
        return None, []
    roles = {}
    spans_per_pid = {}
    for e in mine:
        if e.get("pid") is not None:
            roles.setdefault(e["pid"], e.get("_role")
                             or f"pid{e['pid']}")
            lo, hi = spans_per_pid.get(e["pid"], (e["ts"], e["ts"]))
            spans_per_pid[e["pid"]] = (min(lo, e["ts"]),
                                       max(hi, e["ts"]))
    # monotonic clocks share an epoch per HOST, not across hosts: a
    # request's hops overlap in real time, so per-process ts ranges
    # separated by more than an hour mean files from different hosts
    # were mixed — warn rather than render a silently-wrong ordering
    ranges = sorted(spans_per_pid.values())
    for (_, prev_hi), (lo, _) in zip(ranges, ranges[1:]):
        if lo - prev_hi > 3600_000_000:
            print("timeline: WARNING — per-process timestamp ranges "
                  "are disjoint by over an hour; these trace files "
                  "likely come from different hosts whose monotonic "
                  "clocks are not comparable. Per-hop durations are "
                  "valid; cross-host ordering is not.")
            break
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": role}}
            for pid, role in sorted(roles.items())]
    body = meta + [{k: v for k, v in e.items() if k != "_role"}
                   for e in mine]
    out = out or f"mxtpu_timeline_{'_'.join(sorted(trace_ids))}.json"
    with open(out, "w") as fh:
        fh.write("[\n")
        fh.write(",\n".join(json.dumps(e) for e in body))
        fh.write("\n]\n")
    spans = [e for e in mine if e.get("ph") == "X"]
    names = sorted({e["name"] for e in mine})
    print(f"timeline: {len(mine)} events ({len(spans)} spans) for "
          f"trace {sorted(trace_ids)} across "
          f"{len(roles)} process(es) {sorted(roles.values())}")
    print(f"  events: {', '.join(names)}")
    print(f"  wrote {out} (load in chrome://tracing or Perfetto)")
    return out, mine


def _eng(v):
    """Engineering-notation number for the roofline table columns."""
    if v is None:
        return "-"
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= div:
            return f"{v / div:.2f}{suf}"
    return f"{v:.0f}"


def perf_rows(samples):
    """Join one parsed scrape's ``mxtpu_program_*`` / ``mxtpu_mfu`` /
    ``mxtpu_hbm_bw_util`` samples into roofline-table rows keyed by
    (process, program). ``samples`` is ``parse_prometheus(text)
    ["samples"]`` — so the same function renders an in-process dump, a
    gateway scrape, or a FEDERATED scrape (rows then carry the process
    label). Rows sort by share of attributed wall time within their
    process, descending."""
    rows = {}

    def row(labels):
        d = dict(labels)
        prog = d.get("program")
        if prog is None:
            return None
        return rows.setdefault((d.get("process", ""), prog), {
            "process": d.get("process", ""), "program": prog,
            "flops": None, "bytes_accessed": None,
            "peak_hbm_bytes": None, "roofline": None,
            "mfu": None, "hbm_bw_util": None, "wall_ms": 0.0})

    for (name, labels), value in samples.items():
        base = name[6:] if name.startswith("mxtpu_") else name
        r = row(labels)
        if r is None:
            continue
        if base == "program_flops":
            r["flops"] = value
        elif base == "program_bytes_accessed":
            r["bytes_accessed"] = value
        elif base == "program_peak_hbm_bytes":
            r["peak_hbm_bytes"] = value
        elif base == "program_roofline" and value:
            r["roofline"] = dict(labels).get("class")
        elif base == "mfu":
            r["mfu"] = value
        elif base == "hbm_bw_util":
            r["hbm_bw_util"] = value
        elif base == "program_wall_ms_total":
            r["wall_ms"] = value
    # a row is a program only if the cost catalog saw it (mfu/bw
    # samples alone can't happen, but a scrape may be truncated)
    rows = {k: r for k, r in rows.items()
            if r["flops"] is not None or r["wall_ms"]}
    totals = {}
    for (proc, _), r in rows.items():
        totals[proc] = totals.get(proc, 0.0) + (r["wall_ms"] or 0.0)
    out = []
    for (proc, _), r in sorted(rows.items()):
        t = totals.get(proc, 0.0)
        r["wall_share"] = (r["wall_ms"] or 0.0) / t if t > 0 else 0.0
        out.append(r)
    out.sort(key=lambda r: (r["process"], -r["wall_share"],
                            r["program"]))
    return out


def perf(source: str = ""):
    """``python tools/diagnose.py perf [source]`` — the roofline
    attribution table from ONE /metrics scrape: program, cost-model
    FLOPs and bytes, compute/memory-bound class, live MFU and HBM-BW
    utilization, and each program's share of attributed wall time.

    ``source``: empty reads THIS process's registry (or scrapes
    ``MXTPU_GATEWAY_ADDR`` when set), ``host:port`` scrapes a running
    gateway's /metrics, anything else is a path to a saved scrape."""
    from mxtpu import telemetry
    source = source or os.environ.get("MXTPU_GATEWAY_ADDR", "")
    if not source:
        text, origin = telemetry.prometheus(), "in-process"
    elif os.path.exists(source):
        with open(source) as f:
            text = f.read()
        origin = source
    elif ":" in source:
        host, _, port = source.partition(":")
        try:
            from mxtpu.serve.gateway import GatewayClient
            status, text = GatewayClient(
                host, int(port or 9300), timeout=5.0).get_text("/metrics")
        except Exception as e:
            print(f"perf: {source} unreachable: {e!r}")
            return False
        if status != 200:
            print(f"perf: HTTP {status} from {source}")
            return False
        origin = source
    else:
        print(f"perf: no such file {source!r}")
        return False
    try:
        parsed = telemetry.parse_prometheus(text)
    except ValueError as e:
        print(f"perf: malformed scrape from {origin}: {e}")
        return False
    rows = perf_rows(parsed["samples"])
    print(f"----------Roofline attribution ({origin})----------")
    if not rows:
        print("no mxtpu_program_* samples in scrape (telemetry off, "
              "or no watched program has compiled yet)")
        return False
    multi = any(r["process"] for r in rows)
    hdr = (("process",) if multi else ()) + (
        "program", "flops", "bytes", "class", "mfu", "bw_util",
        "wall%")
    lines = [hdr]
    for r in rows:
        cells = ((r["process"],) if multi else ()) + (
            r["program"], _eng(r["flops"]), _eng(r["bytes_accessed"]),
            r["roofline"] or "-",
            "-" if r["mfu"] is None else f"{r['mfu']:.2%}",
            "-" if r["hbm_bw_util"] is None
            else f"{r['hbm_bw_util']:.2%}",
            f"{r['wall_share']:.1%}")
        lines.append(cells)
    widths = [max(len(row[i]) for row in lines)
              for i in range(len(hdr))]
    for row in lines:
        print("  ".join(c.ljust(w) for c, w in zip(row, widths))
              .rstrip())
    return True


def lint_report(path: str = ""):
    """``python tools/diagnose.py lint [report]`` — per-rule summary
    of an mxlint report. Accepts the SARIF 2.1.0 log the CI mxlint
    stage writes (``--deep --sarif build/mxlint_deep.sarif``) or a
    ``python -m tools.mxlint --json`` findings array. Stdlib-only:
    does not import mxtpu."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = path or os.path.join(repo, "build", "mxlint_deep.sarif")
    if not os.path.exists(path):
        print(f"lint: no report at {path} — generate one with\n"
              f"  python -m tools.mxlint --deep --sarif {path} "
              f"mxtpu/ tools/ bench.py")
        return False
    try:
        with open(path) as f:
            data = json.load(f)
    except ValueError as e:
        print(f"lint: malformed report {path}: {e}")
        return False
    descs, findings = {}, []          # rule -> desc; (rule, site, msg)
    if isinstance(data, dict) and "runs" in data:
        for run in data["runs"]:
            for rule in run.get("tool", {}).get("driver", {}) \
                    .get("rules", []):
                descs[rule["id"]] = rule.get(
                    "shortDescription", {}).get("text", "")
            for res in run.get("results", []):
                loc = (res.get("locations") or
                       [{}])[0].get("physicalLocation", {})
                site = (f"{loc.get('artifactLocation', {}).get('uri', '?')}"
                        f":{loc.get('region', {}).get('startLine', '?')}")
                findings.append((res.get("ruleId", "?"), site,
                                 res.get("message", {}).get("text", "")))
    elif isinstance(data, list):      # tools.mxlint --json
        for f_ in data:
            findings.append((f_.get("rule", "?"),
                             f"{f_.get('path', '?')}:{f_.get('line', '?')}",
                             f_.get("message", "")))
    else:
        print(f"lint: {path} is neither a SARIF log nor an mxlint "
              f"--json array")
        return False
    print(f"----------mxlint report ({path})----------")
    if not findings:
        print(f"clean ({len(descs)} rule(s) ran)")
        return True
    per_rule = {}
    for rule, site, msg in findings:
        per_rule.setdefault(rule, []).append((site, msg))
    lines = [("rule", "count", "first site", "description")]
    for rule in sorted(per_rule):
        group = per_rule[rule]
        lines.append((rule, str(len(group)), group[0][0],
                      descs.get(rule, group[0][1])))
    widths = [max(len(row[i]) for row in lines) for i in range(3)]
    for row in lines:
        print("  ".join(c.ljust(w) for c, w in
                        zip(row[:3], widths)) + "  " + row[3])
    print(f"{len(findings)} finding(s) across {len(per_rule)} rule(s)"
          f" — see docs/lint.md for rule semantics and fixes")
    return True


def _tail_disk_dump(n: int = 20):
    """A crashed process can't answer report() — but its flight dump
    on disk can."""
    path = os.environ.get("MXTPU_TELEMETRY_FLIGHT_PATH", "")
    if not path or not os.path.exists(path):
        return
    print(f"----------On-disk flight dump ({path})----------")
    with open(path) as f:
        lines = f.readlines()[-n:]
    for line in lines:
        try:
            evt = json.loads(line)
        except ValueError:
            print(line.rstrip())
            continue
        print(" ".join(f"{k}={v}" for k, v in evt.items()))


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "perf":
        source = sys.argv[2] if len(sys.argv) > 2 else ""
        sys.exit(0 if perf(source) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "kv":
        addr = sys.argv[2] if len(sys.argv) > 2 else ""
        if not addr and not os.environ.get("MXTPU_GATEWAY_ADDR"):
            print("usage: diagnose.py kv <host:port>  (or set "
                  "MXTPU_GATEWAY_ADDR)")
            sys.exit(2)
        sys.exit(0 if kv_state(addr) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "fleet":
        addr = sys.argv[2] if len(sys.argv) > 2 else ""
        if not addr and not os.environ.get("MXTPU_GATEWAY_ADDR"):
            print("usage: diagnose.py fleet <host:port>  (or set "
                  "MXTPU_GATEWAY_ADDR)")
            sys.exit(2)
        sys.exit(0 if fleet_state(addr) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "flywheel":
        addr = sys.argv[2] if len(sys.argv) > 2 else ""
        if not addr and not os.environ.get("MXTPU_GATEWAY_ADDR"):
            print("usage: diagnose.py flywheel <host:port>  (or set "
                  "MXTPU_GATEWAY_ADDR)")
            sys.exit(2)
        sys.exit(0 if flywheel_state(addr) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "elastic":
        addr = sys.argv[2] if len(sys.argv) > 2 else ""
        if not addr and not os.environ.get("MXTPU_ELASTIC_COORD_ADDR"):
            print("usage: diagnose.py elastic <host:port>  (or set "
                  "MXTPU_ELASTIC_COORD_ADDR)")
            sys.exit(2)
        sys.exit(0 if elastic_state(addr) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "lint":
        path = sys.argv[2] if len(sys.argv) > 2 else ""
        sys.exit(0 if lint_report(path) else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "timeline":
        args = sys.argv[2:]
        if not args:
            print("usage: diagnose.py timeline <rid-or-trace-id> "
                  "[--dir DIR] [--out FILE]")
            sys.exit(2)
        key, trace_dir, out = args[0], None, None
        rest = args[1:]
        while rest:
            flag = rest.pop(0)
            if flag == "--dir" and rest:
                trace_dir = rest.pop(0)
            elif flag == "--out" and rest:
                out = rest.pop(0)
            else:
                print(f"unknown timeline arg {flag!r}")
                sys.exit(2)
        path, _ = timeline(key, trace_dir=trace_dir, out=out)
        sys.exit(0 if path else 1)
    print("----------Python Info----------")
    print("version:", sys.version.replace("\n", " "))
    print("platform:", platform.platform())
    print("----------mxtpu Info----------")
    import mxtpu as mx
    print("mxtpu version:", mx.__version__)
    import jax
    print("jax:", jax.__version__)
    print("devices:", jax.devices())
    print("features:", mx.runtime.Features())
    from mxtpu import native
    print("libmxtpu native:", native.available())
    report()
    gateway_state()
    elastic_state()
    _tail_disk_dump()


if __name__ == "__main__":
    main()
