"""The controls the retention cell's limits are set against, each put
through the harness's OWN comparison, so that it comes out ``correct:
false`` by the code that judges the system (``drivers/serve_family.py``
``check_batch``: ``argmax_gaps`` and the ``check.also`` entries) and a
later session can read the second reading of every limit again:

    chiprun -- python3 benchmark/grid/selftest/retention_controls.py \
        --control operands_e4m3 --seed <n>        (or operands_e5m2)
    chiprun -- python3 benchmark/grid/selftest/retention_controls.py \
        --control state_bf16 --seed <n> [--seconds 51]

``operands_*`` stands the plain reference with every matmul's operands
rounded one precision below the configuration's (3 mantissa bits,
float8_e4m3's, or float8_e5m2 as it is; exponents kept wide, as a
SCALED cast keeps them: at e4m3's own 4 exponent bits
``reduce_precision`` flushes weights of N(0, 1/5120) to zero) in the
program's place: it answers the check batch greedily, a token at a
time, where the gateway would, and hands out the stream entering every
layer where ``layer_streams`` would; ``tol`` and ``layer_tol`` judge
it. It holds no state, so ``state`` is left off its ``also``.
``state_bf16`` is the cell itself (``run.py``, every argument handed
on) with the family's bank held in bfloat16; ``state_tol`` judges it.
The last line of standard output is the control's result."""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
GRID = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(GRID))
# (exponent bits, mantissa bits) an operand is rounded to
OPERANDS = {"operands_e4m3": (5, 3), "operands_e5m2": (5, 2)}


def lowered_reference(file: str, nexp: int, nmant: int):
    """The plain reference of ``reference/<file>.py``, a copy of its
    own, with the operands of every matmul rounded."""
    import jax.numpy as jnp
    from jax import lax
    spec = importlib.util.spec_from_file_location(
        f"grid_reference_{file}_e{nexp}m{nmant}",
        os.path.join(GRID, "reference", file + ".py"))
    low = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(low)
    cut = lambda a: lax.reduce_precision(a, nexp, nmant)
    low.mm = lambda a, b: jnp.matmul(cut(a), cut(b),
                                     precision=lax.Precision.HIGHEST)
    return low


def operands_control(config, traffic, driver, seed, nexp, nmant, log=print):
    """``check_batch`` over the lowered reference. Returns (ok, worst
    token gap, notes) as it does for the system."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from program import seed_key
    module, cfg, reference = driver.family_of(config)
    params = jax.jit(partial(module.init_params, cfg))(seed_key(seed))
    low = lowered_reference(config["family"]["reference"], nexp, nmant)
    check = config["check"]
    pad_to = check["prompt_cap"] + check["new_tokens"]

    answered = []

    def client(plan):
        """Every job answered greedily by the lowered reference: one
        forward over the padded sequence a token (it keeps no state)."""
        recs = []
        for job in plan["jobs"]:
            seq = list(job["prompt"])
            for _ in range(job["max_new_tokens"]):
                toks = jnp.asarray(seq + [0] * (pad_to - len(seq)),
                                   jnp.int32)
                lg = low.logits(config, params, toks,
                                rows=jnp.asarray([len(seq) - 1]))
                seq.append(int(lg[0].argmax()))
            answered.append(jnp.asarray(seq + [0] * (pad_to - len(seq)),
                                        jnp.int32))
            recs.append({"status": 200, "reason": "complete", "error": None,
                         "tokens": seq[len(job["prompt"]):]})
        return recs

    def layer_streams(cfg, params, tokens):
        """(L + 1, 1, s, dim) for the batch's first sequence, which is
        what ``layers_check`` asks for. It asks under ``jax.jit``; the
        reference runs a layer at a time outside it, as it does where
        it judges, so that one layer's float32 weights exist at once."""
        del cfg, tokens
        with jax.ensure_compile_time_eval():
            x = params["tok_embed"][answered[0]].astype(jnp.float32)
            streams = [x]
            for index in range(config["num_hidden_layers"]):
                streams.append(low.layer(config, params, index, streams[-1]))
            return jnp.stack(streams)[:, None]

    judged = dict(config, check=dict(check, also=[
        name for name in check.get("also", ()) if name != "state"]))
    return getattr(driver, "serve_family", driver).check_batch(
        types.SimpleNamespace(client=client), judged,
        types.SimpleNamespace(layer_streams=layer_streams), cfg, reference,
        params, traffic, None, None, log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True,
                    choices=sorted(OPERANDS) + ["state_bf16"])
    ap.add_argument("--workload", default="brumby-longgen-closed16")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, GRID)
    sys.path.insert(0, ROOT)
    if args.control == "state_bf16":
        from functools import partial
        import jax.numpy as jnp
        import mxtpu.models.retention as family
        import run as grid_run
        family.RetentionConfig = partial(family.RetentionConfig,
                                         state_dtype=jnp.bfloat16)
        return grid_run.main(["--workload", args.workload, "--seed",
                              str(args.seed), "--seconds", str(args.seconds),
                              "--trace", "0"])
    from mxtpu import runtime
    runtime.use_compile_cache()
    import gen
    import run as grid_run
    parts = grid_run.load_cell(grid_run.load_json(ROOT, "BENCHMARK.json"),
                               args.workload)
    grid_run.device_or_die(parts["cell"]["chips"])
    config = parts["config"]
    ok, worst, notes = operands_control(
        config, gen.Traffic(parts["traffic"], args.seed,
                            config["vocab_size"]),
        grid_run.load_module("drivers", config["kind"]), args.seed,
        *OPERANDS[args.control])
    print(json.dumps({"control": args.control, "seed": args.seed,
                      "correct": bool(ok), "check_worst_gap": worst,
                      "check_tol": config["check"]["tol"], **notes}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
