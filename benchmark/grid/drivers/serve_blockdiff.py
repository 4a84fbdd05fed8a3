"""``drivers/serve_family.py`` for a family that generates by diffusion
over blocks (``mxtpu/models/blockdiff_moe.py``): a step yields no token
or a block a slot, so an emitted token has no position of its own in a
causal pass and ``serve_family``'s token check (one forward, token i
against row i - 1) does not apply. This file swaps in a token REPLAY
(``reference.argmax_gaps``: block by block, pass by pass, teacher-forced
with the tokens the system emitted) and adds three checks of what no
emitted token shows to ``serve_family``'s ``ALSO``, each with a limit of
its own in ``check``:

``router_softmax``  ``parallel.moe.route_softmax`` against the
                    reference's ``route`` on the same inputs
                    (``router_tol``); the near-tie flips over the first
                    sequence are noted;
``unmask``          the program's candidates, confidences, ranking and
                    transfer (``unmask``) against the reference's on the
                    SAME logits (``conf_tol``);
``passes``          the family's step program at the engine's shapes
                    beside the engine, several slots out of phase over
                    pages a prefill of its own wrote: every pass's
                    logits at a block's rows against the reference's
                    full forward on the same tokens (``pass_tol``).

Everything else is ``serve_family``'s: its ``run`` is handed on, with
the share of block waits that hold a prefill noted."""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "grid_drivers_serve_family", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "serve_family.py"))
serve_family = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_family)


# the passes check's prompt: 16 whole blocks and the remainder
PASS_PROMPT = 64


def _pad_to(config):
    """The one length every reference forward of a check runs at (the
    block-causal mask keeps what follows a position out of it)."""
    check, B = config["check"], config["block_length"]
    return -(-(check["prompt_cap"] + check["new_tokens"]) // B) * B + B


def check_batch(serve, config, module, cfg, reference, params, traffic,
                host, port, log):
    """``serve_family.check_batch`` with the replay in the token
    comparison's place: greedy requests through the gateway while
    nothing else runs; the reference replays each request's blocks
    (``check.tol``; ``check_order_retries`` blocks needed another order
    than the reference's own, which stood ``check_order_conf_under``
    under it at most); then the checks ``check.also`` names. Returns
    (ok, worst token gap, notes)."""
    import jax.numpy as jnp
    import numpy as np
    check = config["check"]
    jobs = traffic.check_batch(check["n"], check["prompt_cap"],
                               check["new_tokens"])
    recs = serve.client({"mode": "batch", "host": host, "port": port,
                         "jobs": jobs, "together": True})
    pad_to = _pad_to(config)
    worst, ok, seqs, replay = 0.0, True, [], {}
    for job, rec in zip(jobs, recs):
        if (rec["status"] != 200 or rec["reason"] != "complete"
                or len(rec["tokens"]) != check["new_tokens"]):
            log(f"# check request {job['id']} came back {rec['status']}"
                f" {rec['reason']} {rec['error']}")
            return False, float("nan"), {}
        if config["mask_token_id"] in rec["tokens"]:
            log(f"# check request {job['id']} emitted the mask id")
            return False, float("nan"), {}
        gaps = np.asarray(reference.argmax_gaps(
            config, params, job["prompt"], rec["tokens"], check["tol"],
            notes=replay, pad_to=pad_to))
        seq = job["prompt"] + rec["tokens"]
        seqs.append((jnp.asarray(seq + [0] * (pad_to - len(seq)),
                                 jnp.int32), len(job["prompt"])))
        worst = max(worst, float(gaps.max()))
        ok = ok and bool(np.all(np.isfinite(gaps)))
        log(f"# check {job['id']}: prompt {len(job['prompt'])} (remainder "
            f"{len(job['prompt']) % config['block_length']}), worst gap "
            f"{float(gaps.max()):.4f} at token {int(gaps.argmax())}")
    ok = ok and worst <= check["tol"]
    notes = {"check_order_retries": replay.get("order_retries", 0),
             "check_order_conf_under": replay.get("order_conf_under", 0.0)}
    for name in check.get("also", ()):
        fine, more = serve_family.ALSO[name](
            config, module, cfg, reference, params, seqs, traffic.seed, log)
        ok = ok and fine
        notes.update(more)
    return ok, worst, notes


def router_softmax_check(config, module, cfg, reference, params, seqs,
                         seed, log):
    """``serve_family.router_check`` for the softmax router (that one is
    written for ``route_sigmoid`` and its bias). (1) ``router_gap``: the
    program's router and the reference's on the SAME inputs
    (``check.prompt_cap`` rows of the activations' type from the seed,
    the last layer's router weights) as the largest difference of any
    expert's weight; held to ``check.router_tol``. (2) ``router_flips``:
    over the first sequence (prompt + emitted), the (token, layer)
    places where the program's picks in its own precision
    (``router_picks``) are not the reference's set; reported, and
    carried by ``check.tol``. Returns (ok, notes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxtpu.parallel import moe
    from program import seed_key
    check = config["check"]
    w = params["layers"]["router"][-1]
    x = jax.random.normal(jax.random.fold_in(seed_key(seed), 31),
                          (check["prompt_cap"], w.shape[0]), cfg.dtype)
    kw = dict(top_k=cfg.experts_per_tok, renorm=cfg.norm_topk_prob)
    idx, wts = jax.jit(lambda x, w: moe.route_softmax(x, w, **kw))(x, w)
    got = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].set(wts)
    _, want = jax.jit(lambda x, w: reference.route(
        x.astype(jnp.float32), w.astype(jnp.float32), kw["top_k"],
        kw["renorm"]))(x, w)
    gap = float(jnp.abs(got - want).max())
    toks = seqs[0][0]
    theirs = []
    reference.logits(config, params, toks, rows=jnp.arange(1), picks=theirs)
    mine = np.sort(np.asarray(jax.jit(
        lambda p, t: module.router_picks(cfg, p, t))(params, toks[None])), -1)
    theirs = np.sort(np.stack([np.asarray(p) for p in theirs]), -1)
    by_layer = (mine != theirs).any(-1).sum(-1)
    flips, places = int(by_layer.sum()), mine.shape[0] * mine.shape[1]
    log(f"# check router_softmax: weight gap {gap:.3g} (limit "
        f"{check['router_tol']}); {flips} of {places} (token, layer) picks "
        f"are not the float32 reference's, by layer {by_layer.tolist()}")
    return gap <= check["router_tol"], {
        "check_router_gap": gap, "check_router_tol": check["router_tol"],
        "check_router_flips": flips, "check_router_places": places,
        "check_router_flips_by_layer": by_layer.tolist()}


def unmask_check(config, module, cfg, reference, params, seqs, seed, log):
    """The unmasking on the SAME logits: ``run.engine.max_slots`` x B x
    vocabulary float32 from the seed (normal x 1.5; one row in four has
    one id raised by 15, so that its confidence passes the dynamic
    threshold), some positions already filled, through the program's
    ``unmask`` and the reference's ``confidence`` and ``transfer``,
    greedy and sampled (``check.sampling``, the cell's; the draw is
    the program's and the reference says what it was worth), static and
    dynamic. Held: a greedy row's candidate is the reference's argmax; a
    sampled candidate lies in the reference's nucleus; the positions
    that take their candidates are the reference's transfer of the
    program's confidences; and those confidences agree with the
    reference's to ``check.conf_tol`` as the largest ``|got - want| /
    want`` (whether the positions are also the transfer of the
    REFERENCE's confidences is noted: two confidences closer than the
    limit may rank either way round). Returns (ok, notes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dataclasses import replace
    from program import seed_key
    del params, seqs
    check = config["check"]
    S, B, V = config["run"]["engine"]["max_slots"], cfg.block_length, \
        cfg.vocab_size
    key = jax.random.fold_in(seed_key(seed), 37)
    k_lg, k_peak, k_at, k_mask, k_draw = jax.random.split(key, 5)
    lg = 1.5 * jax.random.normal(k_lg, (S, B, V), jnp.float32)
    peak = jax.random.randint(k_at, (S, B), 0, V - 1)
    peak = jnp.where(peak >= cfg.mask_token_id, peak + 1, peak)
    lg = lg + jnp.where(
        (jax.random.uniform(k_peak, (S, B)) < 0.25)[..., None]
        & (jnp.arange(V) == peak[..., None]), 15.0, 0.0)
    masked = jax.random.uniform(k_mask, (S, B)) < 0.7
    masked = masked.at[:, 0].set(True)       # every slot has one to fill
    keys = jax.random.split(k_draw, S)
    samp = check["sampling"]
    lg_np, masked_np = np.asarray(lg), np.asarray(masked)
    worst, same, as_ref, inside, cases = 0.0, True, True, True, {}
    for remasking in ("low_confidence_static", "low_confidence_dynamic"):
        variant = replace(cfg, remasking=remasking)
        model = dict(config, remasking=remasking)
        run = jax.jit(lambda lg, m, k, t, p: module.unmask(
            variant, lg, m, k, t, jnp.full((S,), V, jnp.int32), p))
        for name, temp, top_p in (("greedy", 0.0, 1.0),
                                  ("sampled", samp["temperature"],
                                   samp["top_p"])):
            x0, conf, take, _ = (np.asarray(a) for a in run(
                lg, masked, keys, jnp.full((S,), temp, jnp.float32),
                jnp.full((S,), top_p, jnp.float32)))
            gap, moved = 0.0, 0
            for s in range(S):
                want_x0, want = reference.confidence(
                    model, lg_np[s], x0=x0[s], temperature=temp,
                    top_p=top_p)
                # the transfer on the program's own confidences (two
                # that lie within conf_tol of each other may rank either
                # way round: the gap below holds the confidences)
                want_take, _ = reference.transfer(model, conf[s],
                                                  masked_np[s])
                same = same and bool((want_take == take[s]).all()) \
                    and bool((want_x0 == x0[s]).all())
                as_ref = as_ref and bool((reference.transfer(
                    model, want, masked_np[s])[0] == take[s]).all())
                inside = inside and bool((want > 0).all())
                gap = max(gap, float(np.max(
                    np.abs(conf[s] - want) / np.maximum(want, 1e-30))))
                moved += int(take[s].sum())
            cases[f"{name}.{remasking.rsplit('_', 1)[1]}"] = {
                "conf_gap": gap, "positions_taken": moved}
            worst = max(worst, gap)
    log(f"# check unmask: {S} x {B} x {V} logits, confidence gap {worst:.3g}"
        f" (limit {check['conf_tol']}); the same candidates and positions: "
        f"{same} (by the reference's own confidences: {as_ref}); every draw in the reference's nucleus: {inside}; "
        f"{cases}")
    return same and inside and worst <= check["conf_tol"], {
        "check_conf_gap": worst, "check_conf_tol": check["conf_tol"],
        "check_unmask_same": same, "check_unmask_as_reference": as_ref,
        "check_unmask_in_nucleus": inside,
        "check_unmask_cases": cases}


def pass_prompts(config, seqs):
    """The prompts ``passes`` seats, one a live slot: check sequence ``i
    % len(seqs)`` cut to its first ``PASS_PROMPT`` tokens and a remainder
    of ``i % B`` (one wrong key counts for most where the keys are few,
    and for as much under every seed; every remainder is there)."""
    import numpy as np
    B = config["block_length"]
    out = []
    for i in range(config["check"]["pass_slots"]):
        toks, n = seqs[i % len(seqs)]
        out.append(np.asarray(toks)[:min(n, PASS_PROMPT + i % B)])
    return out


def pass_errors(got, want):
    """Each row's ``|got - want|`` over ``|want - mean(want)|``, the
    logits' spread."""
    import numpy as np
    spread = np.linalg.norm(want - want.mean(-1, keepdims=True), axis=-1)
    return (np.linalg.norm(got - want, axis=-1) / spread).tolist()


def passes_check(config, module, cfg, reference, params, seqs, seed, log):
    """Prefill, tentative writes, the in-block keys and the commit
    THROUGH THE CACHE against no cache at all, on the program the window
    times: the family's step over a bank of the ENGINE's shapes
    (``run.engine``'s slots, pages, page size and chunk; the engine's
    jits: the function, its name, its donation and its operands' types,
    so the compiled program is the engine's own, from the cache) beside
    the engine, over pools of its own whose page table is a permutation
    of the pool from the seed. ``check.pass_slots`` slots spread over the
    bank are live (:func:`pass_prompts`); slot ``i`` is seated, in chunks
    of ``run.engine.prefill_chunk`` through the stage, after ``i`` passes
    of the bank, so the slots run OUT OF PHASE (one mid-block while
    another commits and a third is seated), each greedily for
    ``check.pass_blocks`` blocks and then idle. Slot 0 is seated with a
    prompt reversed and never runs: it has to hold its block state bit
    for bit. Before every pass the logits the step is about to read at a
    running slot's rows (``decode_logits``) are held against the
    reference's full forward over the slot's prefix and its block as it
    then stands (tokens, ``[MASK]`` where masked), and the bank's state is
    advanced by the step program itself (``block_step_slots_paged``). A
    row's error is :func:`pass_errors`; the MEDIAN row is held to
    ``check.pass_tol`` (a row whose router pick parts from the
    reference's reads a whole expert's output, as ``layer_tol``'s why
    says). Returns (ok, notes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    check, eng = config["check"], config["run"]["engine"]
    B, ps, chunk = cfg.block_length, eng["page_size"], eng["prefill_chunk"]
    S, per_slot = eng["max_slots"], -(-eng["max_len"] // ps)
    prompts = pass_prompts(config, seqs)
    pad_to = int(seqs[0][0].size)
    live = np.linspace(1, S - 1, len(prompts)).astype(int)

    def program(fn, name, donate):
        named = partial(fn, cfg, mesh=None)
        named.__name__ = named.__qualname__ = name
        return jax.jit(named, donate_argnums=donate)

    state = module.init_paged_cache(cfg, S, eng["n_pages"], ps)
    sv = {n: state.pop(n) for n in module.SLOT_VARS}
    kv = state
    stage = module.init_prefill_stage(cfg, per_slot * ps, chunk)
    first = program(module.prefill_slot_paged_chunk,
                    f"prefill_slot_paged_chunk_b{chunk}", (3,))
    last = program(module.prefill_slot_paged_last,
                   f"prefill_slot_paged_last_b{chunk}", (7,))
    step = program(module.block_step_slots_paged, "block_step_slots_paged",
                   (1,))
    peek = jax.jit(lambda p, kv, sv, a, pt: module.decode_logits(
        cfg, p, kv, sv, a, pt)[0][live])
    table = 1 + np.random.default_rng(seed).permutation(
        eng["n_pages"] - 1)[:S * per_slot].reshape(S, per_slot).astype(
            np.int32)
    greedy = (np.zeros(S, np.float32), np.full(S, cfg.vocab_size, np.int32),
              np.ones(S, np.float32))

    def seat(prompt, slot, stage, kv, sv):
        for done in range(0, prompt.size, chunk):
            left = prompt.size - done
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :min(left, chunk)] = prompt[done:done + chunk]
            if left > chunk:
                stage = first(params, padded, np.int32(done), stage)
            else:
                _, kv, sv = last(
                    params, padded, np.int32(done), np.int32(left), stage,
                    table[slot].copy(), np.int32(slot), kv, sv,
                    jax.random.PRNGKey(0), *(a[0] for a in greedy))
        return stage, kv, sv

    stage, kv, sv = seat(prompts[0][::-1].copy(), 0, stage, kv, sv)
    idle = {n: np.asarray(a[0]) for n, a in sv.items()}
    # what each live slot holds: its prefix as committed so far
    held = [np.zeros(pad_to, np.int32) for _ in prompts]
    blocks = np.zeros(len(prompts), int)
    active = np.zeros(S, bool)
    gaps, passes, abreast = [], 0, 0
    while (blocks < check["pass_blocks"]).any():
        if passes < len(prompts):
            stage, kv, sv = seat(prompts[passes], live[passes], stage, kv, sv)
            held[passes][:prompts[passes].size] = prompts[passes]
            active[live[passes]] = True
        mine = {n: np.asarray(sv[n])[live] for n in
                ("lengths", "masked", "tokens")}
        got = np.asarray(peek(params, kv, sv, active.copy(), table))
        for i in np.flatnonzero(active[live]):
            length = int(mine["lengths"][i])
            seq = held[i].copy()
            seq[length:length + B] = np.where(
                mine["masked"][i], cfg.mask_token_id, mine["tokens"][i])
            gaps += pass_errors(got[i], np.asarray(reference.logits(
                config, params, seq, rows=jnp.arange(length, length + B))))
        abreast = max(abreast, int(active.sum()))
        out, kv, sv = step(params, kv, sv, active.copy(), table, *greedy)
        emit = np.asarray(out)[S * B:2 * S * B].reshape(S, B)[live] > 0
        passes += 1
        for i in np.flatnonzero(emit.any(-1)):
            # the blocks a slot commits are a check sequence's own only
            # as far as the engine's run took the same near ties: the
            # replay's prefix follows what THIS slot committed
            length = int(mine["lengths"][i])
            held[i][length:length + B] = np.asarray(sv["tokens"])[live[i]]
            blocks[i] += 1
            active[live[i]] = blocks[i] < check["pass_blocks"]
    gap = float(np.median(gaps))
    kept = all(np.array_equal(np.asarray(sv[n][0]), idle[n]) for n in idle)
    path = module.decode_attention_path(cfg, kv)
    log(f"# check passes: a bank of {S} slots over {eng['n_pages']} pages, "
        f"prompts of {[int(p.size) for p in prompts]} seated a pass apart "
        f"in slots {live.tolist()} (at most {abreast} abreast), "
        f"{int(blocks.sum())} blocks in {passes} passes of the bank "
        f"({path}): median row error over the logits' spread {gap:.4g}, "
        f"largest {max(gaps):.4g}, {len(gaps)} rows (limit "
        f"{check['pass_tol']}); the idle slot kept its block: {kept}")
    return kept and gap <= check["pass_tol"], {
        "check_pass_gap": gap, "check_pass_gap_max": float(max(gaps)),
        "check_pass_tol": check["pass_tol"], "check_pass_count": passes,
        "check_pass_rows": len(gaps), "check_pass_abreast": abreast,
        "check_pass_path": path, "check_pass_idle_kept": kept}


def run(parts, device, seed, seconds, trace, t_process, log):
    """``serve_family.run``; the line also notes the share of block
    waits (``denoising_steps + 1`` passes of a slot: the gap a stream's
    ``itl_p95_ms`` is) that held a prefill chunk, from the window's
    prefills over its steps, and, traced or not, what a window's pass
    cost and what it read (``window_*``: which of the seed's weights and
    traffic moved ``itl_p95_ms`` can be told from a run's own line)."""
    from program_reads import hist_sum
    obs = serve_family.run(parts, device, seed, seconds, trace, t_process,
                           log)
    notes = obs["notes"]
    share = notes.get("gaps_with_chunk_share")
    if share is not None:
        notes["block_waits_with_prefill_share"] = min(
            100.0, share * (parts["config"]["denoising_steps"] + 1))
    steps = obs["scrape1"].get("serve_steps_total", 0.0) \
        - obs["scrape0"].get("serve_steps_total", 0.0)
    wait = hist_sum(obs, "span_serve_readback_ms")
    if steps:
        notes["window_step_ms"] = 1e3 * seconds / steps
        notes["window_prefills"] = hist_sum(obs, "span_serve_prefill_ms",
                                            "_count")
        if wait is not None:
            notes["window_readback_wait_share"] = wait / (10.0 * seconds)
    touched = serve_family._load(
        "readers", "moe_experts_touched_mean.py").read(obs)
    if touched is not None:
        notes["window_experts_touched_mean"] = float(touched)
    return obs


serve_family.check_batch = check_batch
serve_family.ALSO.update(router_softmax=router_softmax_check,
                         unmask=unmask_check, passes=passes_check)
family_of = serve_family.family_of
