"""The controls the block-diffusion cell's limits are set against, each
put through the harness's OWN comparison (``drivers/serve_blockdiff.py``:
the replay and the ``check.also`` entries), so that it comes out
``correct: false`` by the code that judges the system and a later
session can read the second reading of every limit again:

    chiprun -- python3 benchmark/grid/selftest/blockdiff_controls.py \
        --control commit_skipped|blind_block|wrong_slot|conf_bf16|router_bf16 \
        --seed <n>
    chiprun -- python3 benchmark/grid/selftest/blockdiff_controls.py \
        --control operands_e4m3 --seed <n>

All but the last are the cell itself (``run.py``, a short window) with
one fault put into the family's programs (:func:`apply`), so into the
engine's step and into the ``passes`` check's, which is the same
program:
``commit_skipped`` emits a block and advances the length in the pass
that fills its last mask, so the keys and values the block leaves behind
were computed with that position still ``[MASK]``; ``blind_block`` lets
a block's rows attend the cache alone, not the block's own keys;
``wrong_slot`` walks the NEXT slot's row of the page table (a slot's
keys are another request's, or the scratch page's), which is ``tol``'s
upper reading: a token drawn from another context; ``conf_bf16`` takes the candidates' confidence from logits rounded to
bfloat16, so the ranking is bfloat16's; ``router_bf16`` HOLDS the
router's product in bfloat16 before the softmax (``lax.reduce_precision``:
a pair of casts is compiled away). ``passes`` judges the first two,
the tokens' replay and ``passes`` the third, ``unmask`` the fourth,
``router_softmax`` the fifth.

``operands_e4m3`` stands the plain reference with every matmul's
operands rounded one precision below the configuration's (3 mantissa
bits, float8_e4m3's, the exponent kept wide as a scaled cast keeps it) in
the program's place: it answers the check batch greedily by its own
loop, hands out the stream entering every layer where ``layer_streams``
would, and its own loop's passes over ``passes``' prompts (the
driver's ``pass_prompts``, ``check.pass_blocks`` blocks each) stand
where the step program's would (``check_pass_gap``: the same rows'
error over the logits' spread);
``tol``, ``layer_tol`` and ``pass_tol`` judge it. The last line of
standard output is the control's result."""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
GRID = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(GRID))
FAULTS = ("commit_skipped", "blind_block", "wrong_slot", "conf_bf16",
          "router_bf16")


def apply(control: str):
    """Put the fault ``control`` into ``mxtpu.models.blockdiff_moe``;
    returns a function that takes it out again."""
    import jax.numpy as jnp
    import mxtpu.models.blockdiff_moe as family
    kept = {n: getattr(family, n) for n in (
        "block_step_slots_paged", "paged_block_attention", "unmask")}

    if control == "commit_skipped":
        def step(cfg, params, kv, sv, active, page_table, *sampling,
                 mesh=None):
            out, pools, new = kept["block_step_slots_paged"](
                cfg, params, kv, sv, active, page_table, *sampling)
            S, B = sv["tokens"].shape
            done = sv["masked"].any(-1) & ~new["masked"].any(-1) & active
            grew = jnp.where(done, B, 0).astype(jnp.int32)
            new = dict(new, lengths=sv["lengths"].astype(jnp.int32) + grew,
                       masked=new["masked"] | done[:, None],
                       fresh=new["fresh"] | done[:, None],
                       passes=jnp.where(done, 0, new["passes"]))
            emit = (done[:, None] & sv["fresh"]).reshape(-1)
            return out.at[S * B:2 * S * B].set(
                emit.astype(out.dtype)), pools, new
        step.__name__ = "block_step_slots_paged"
        family.block_step_slots_paged = step
    elif control == "blind_block":
        def attend(q, k, v, table, lengths, **kw):
            return kept["paged_block_attention"](
                q, k, v, table, jnp.maximum(lengths - q.shape[2], 0), **kw)
        family.paged_block_attention = attend
    elif control == "wrong_slot":
        def attend(q, k, v, table, lengths, **kw):
            return kept["paged_block_attention"](
                q, k, v, jnp.roll(table, -1, axis=0), lengths, **kw)
        family.paged_block_attention = attend
    elif control == "conf_bf16":
        from jax import lax

        def unmask(cfg, logits, *rest):
            # held, not cast there and back: a pair of casts is compiled
            # away on the chip
            return kept["unmask"](cfg, lax.reduce_precision(logits, 8, 7),
                                  *rest)
        family.unmask = unmask
    elif control == "router_bf16":
        import jax
        from jax import lax
        from mxtpu.parallel import moe
        kept_router = moe.route_softmax

        def route(x, w_router, *, top_k, renorm=True):
            with jax.named_scope(moe.ROUTER_SCOPE):
                held = lax.reduce_precision(jnp.matmul(
                    x.astype(jnp.float32), w_router.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST), 8, 7)
                w, idx = lax.top_k(jax.nn.softmax(held, axis=-1), top_k)
                if renorm:
                    w = w / w.sum(-1, keepdims=True)
                return idx.astype(jnp.int32), w
        moe.route_softmax = route
        kept["route"] = kept_router
    else:
        raise ValueError(f"no control {control!r}")

    def undo():
        from mxtpu.parallel import moe
        moe.route_softmax = kept.pop("route", moe.route_softmax)
        for n, fn in kept.items():
            setattr(family, n, fn)
    return undo


def operands_control(config, traffic, driver, seed, nexp=5, nmant=3,
                     log=print):
    """The driver's ``check_batch`` over the lowered reference. Returns
    (ok, worst token gap, notes) as it does for the system."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from program import seed_key
    from retention_controls import lowered_reference
    module, cfg, reference = driver.family_of(config)
    params = jax.jit(partial(module.init_params, cfg))(seed_key(seed))
    low = lowered_reference(config["family"]["reference"], nexp, nmant)
    check, B = config["check"], config["block_length"]
    pad_to = driver._pad_to(config)
    answered, errors = [], []

    def padded(seq):
        return list(seq) + [0] * (pad_to - len(seq))

    # the lowered loop's forwards over the one padded shape (its
    # ``generate`` finds ``logits`` in the copy's own globals)
    whole = low.logits
    low.logits = lambda model, params, tokens, rows=None: whole(
        model, params, padded(tokens), rows=rows)

    def client(plan):
        """Every job answered greedily by the lowered reference's own
        loop."""
        recs = []
        for job in plan["jobs"]:
            toks = low.generate(config, params, job["prompt"],
                                job["max_new_tokens"])
            seq = list(job["prompt"]) + toks
            answered.append((jnp.asarray(padded(seq), jnp.int32),
                             len(job["prompt"])))
            recs.append({"status": 200, "reason": "complete", "error": None,
                         "tokens": toks})
        return recs

    def layer_streams(cfg, params, tokens):
        del cfg, tokens
        with jax.ensure_compile_time_eval():
            x = params["tok_embed"][answered[0][0]].astype(jnp.float32)
            streams = [x]
            for index in range(config["num_hidden_layers"]):
                streams.append(low.layer(config, params, index, streams[-1]))
            return jnp.stack(streams)[:, None]

    judged = dict(config, check=dict(check, also=["layers"]))
    ok, worst, notes = driver.check_batch(
        types.SimpleNamespace(client=client), judged,
        types.SimpleNamespace(layer_streams=layer_streams), cfg, reference,
        params, traffic, None, None, log)
    # ``pass_tol``'s second reading: the lowered loop's own passes over
    # the prompts ``passes`` seats, row for row against the float32
    # reference on the same tokens
    for prompt in driver.pass_prompts(config, answered):
        trace = []
        toks = low.generate(config, params, prompt.tolist(),
                            check["pass_blocks"] * B, trace)
        seq = prompt.tolist() + toks
        for start, fed, _, got in trace:
            errors.extend(driver.pass_errors(got, np.asarray(
                reference.logits(
                    config, params, padded(seq[:start] + fed.tolist()),
                    rows=jnp.arange(start, start + B)))))
    gap = float(np.median(errors))
    notes.update(check_pass_gap=gap, check_pass_gap_max=float(max(errors)),
                 check_pass_tol=check["pass_tol"])
    return ok and gap <= check["pass_tol"], worst, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True,
                    choices=FAULTS + ("operands_e4m3",))
    ap.add_argument("--workload", default="sdar-reason-closed32")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, GRID)
    sys.path.insert(0, ROOT)
    import run as grid_run
    if args.control in FAULTS:
        apply(args.control)
        return grid_run.main(["--workload", args.workload, "--seed",
                              str(args.seed), "--seconds", str(args.seconds),
                              "--trace", "0"])
    from mxtpu import runtime
    runtime.use_compile_cache()
    import gen
    parts = grid_run.load_cell(grid_run.load_json(ROOT, "BENCHMARK.json"),
                               args.workload)
    grid_run.device_or_die(parts["cell"]["chips"])
    config = parts["config"]
    ok, worst, notes = operands_control(
        config, gen.Traffic(parts["traffic"], args.seed,
                            config["vocab_size"]),
        grid_run.load_module("drivers", config["kind"]), args.seed)
    print(json.dumps({"control": args.control, "seed": args.seed,
                      "correct": bool(ok), "check_worst_gap": worst,
                      "check_tol": config["check"]["tol"], **notes}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
