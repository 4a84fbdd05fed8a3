"""The controls the Nemotron-H cell's limits are set against, each put
through the harness's OWN comparison (``drivers/serve_family_ssd.py``:
``check_batch`` and the ``check.also`` entries), so that it comes out
``correct: false`` by the code that judges the system, and the second
reading of every limit can be taken again, on a TPU host:

    python3 benchmark/grid/selftest/nemotron_h_controls.py \\
        --control relu|shared_dropped|rope|wrong_slot|state_bf16 \\
        --seed <n>              (or step_e4m3, router_bf16)
    python3 benchmark/grid/selftest/nemotron_h_controls.py \\
        --control operands_e4m3 --seed <n>

All but the last are the cell itself (``run.py``, a short window) with
one fault put into the family's programs (:func:`apply`), so into the
programs the engine runs, whose bank the ``state`` check reads:
``relu`` takes ``relu(x)`` where the experts (routed and shared) take
``relu(x)^2``; ``shared_dropped`` leaves the shared expert out;
``rope`` applies rotate-half RoPE (theta 10000, the key the published
code does not read) to the attention layer's queries and keys where
they are projected;
``wrong_slot`` has every slot's decode step read the NEXT slot's Mamba
state and walk the next slot's row of the page table (its state and keys
are another request's, or an empty slot's); ``state_bf16`` holds the
Mamba state in bfloat16 in the bank and the stage (every chunk and step
rounds it); ``step_e4m3`` rounds the decode step's recurrence operands
(x, dt, B, C) to float8_e4m3's 3 mantissa bits, the exponent kept wide
(``lax.reduce_precision``), and leaves the prefill as it is;
``router_bf16`` HOLDS the router's product in bfloat16 before the sigmoid
(``mxtpu.parallel.moe.route_sigmoid``, which the program and the
``router`` check call). ``tol``, ``layer_tol`` and ``state_tol`` judge
the first four, ``state_tol`` the fifth and sixth, ``router_tol`` the
seventh.

``operands_e4m3`` stands the plain reference with every matmul's
operands rounded one precision below the configuration's (3 mantissa
bits, float8_e4m3's, the exponent kept wide as a scaled cast keeps it) in
the program's place: it answers the check batch greedily, a forward over
the padded sequence a token, and hands out the stream entering every
layer where ``layer_streams`` would (``retention_controls.
operands_control``); ``tol`` and ``layer_tol`` judge it (:func:`judged`).
The last line of standard output is the control's result."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
GRID = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(GRID))
FAULTS = ("relu", "shared_dropped", "rope", "wrong_slot", "state_bf16",
          "step_e4m3", "router_bf16")


def apply(control: str):
    """Put the fault ``control`` into ``mxtpu.models.nemotron_h`` (or the
    expert bank it calls); returns a function that takes it out again."""
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax import lax
    import mxtpu.models.nemotron_h as family
    from mxtpu.models import llama
    from mxtpu.parallel import moe
    kept = {"relu2": moe.relu2, "route_sigmoid": moe.route_sigmoid}
    kept.update({n: getattr(family, n) for n in (
        "_shared_expert", "ssd_step", "_attend", "_qkv", "NemotronHConfig")})
    if control == "relu":
        moe.relu2 = jax.nn.relu
    elif control == "shared_dropped":
        family._shared_expert = lambda lp, h: jnp.zeros(
            (h.shape[0], lp["ws_down"].shape[-1]), h.dtype)
    elif control == "rope":
        def qkv(cfg, lp, h, positions):
            q, k, v = kept["_qkv"](cfg, lp, h, positions)
            b, s, _ = h.shape
            hd = cfg.head_dim
            inv = 1.0 / (10000.0 ** (jnp.arange(0, hd, 2) / hd))
            ang = positions.astype(jnp.float32)[:, None, :, None] * inv
            rope = partial(llama.apply_rope, cos=jnp.cos(ang),
                           sin=jnp.sin(ang))
            k = rope(k.reshape(b, s, -1, hd).transpose(0, 2, 1, 3))
            return rope(q), k.transpose(0, 2, 1, 3).reshape(b, s, -1), v
        family._qkv = qkv
    elif control == "state_bf16":
        family.NemotronHConfig = partial(kept["NemotronHConfig"],
                                         state_dtype=jnp.bfloat16)
    elif control == "wrong_slot":
        def step(state, *rest):
            return kept["ssd_step"](jnp.roll(state, -1, axis=0), *rest)

        def attend(cfg, q, ck, cv, table, *rest):
            return kept["_attend"](cfg, q, ck, cv, jnp.roll(table, -1, 0),
                                   *rest)
        family.ssd_step, family._attend = step, attend
    elif control == "step_e4m3":
        def step(state, x, dt, A, B, C, D):
            low = [lax.reduce_precision(a, 8, 3) for a in (x, dt, B, C)]
            return kept["ssd_step"](state, low[0], low[1], A, low[2],
                                    low[3], D)
        family.ssd_step = step
    elif control == "router_bf16":
        def route(x, w_router, bias, *, top_k, renorm=True, scale=1.0):
            # held, not cast there and back: a pair of casts is compiled
            # away on the chip
            s = jax.nn.sigmoid(lax.reduce_precision(jnp.matmul(
                x.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), 8, 7))
            _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
            w = jnp.take_along_axis(s, idx, axis=-1)
            if renorm:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            return idx.astype(jnp.int32), w * scale
        moe.route_sigmoid = route
    else:
        raise ValueError(f"no control {control!r}")

    def undo():
        moe.relu2 = kept.pop("relu2")
        moe.route_sigmoid = kept.pop("route_sigmoid")
        for n, fn in kept.items():
            setattr(family, n, fn)
    return undo


def judged(config):
    """The configuration as the lowered reference is judged by it: on
    the tokens (``tol``) and each layer alone (``layer_tol``). It has no
    router product of the program's to read, and holds no state."""
    return dict(config, check=dict(config["check"], also=["layers"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True,
                    choices=FAULTS + ("operands_e4m3",))
    ap.add_argument("--workload", default="nemotron3-longctx-closed32")
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
    from retention_controls import OPERANDS, operands_control
    parts = grid_run.load_cell(grid_run.load_json(ROOT, "BENCHMARK.json"),
                               args.workload)
    grid_run.device_or_die(parts["cell"]["chips"])
    config = parts["config"]
    ok, worst, notes = operands_control(
        judged(config), gen.Traffic(parts["traffic"], args.seed,
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
