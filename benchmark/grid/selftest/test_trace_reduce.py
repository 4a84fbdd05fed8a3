"""The trace reduction's second step on an event list written by hand,
with a known idle share, nesting and gaps."""
import math

import trace_reduce as tr


def ev(kind, name, t0, t1, dev=0):
    return {"dev": dev, "kind": kind, "name": name, "t0": t0, "t1": t1}


EVENTS = [
    ev("marker", tr.MARKER, 0.0, 10.0, dev=-1),
    # program A: 1..4, a while holding two ops, then a lone op
    ev("module", "jit_a(11)", 1.0, 4.0),
    ev("op", "while.1", 1.0, 3.0),
    ev("op", "fusion.1", 1.0, 2.0),
    ev("op", "fusion.2", 2.0, 2.5),
    ev("op", "all-gather.3", 3.0, 4.0),
    # in flight over the all-gather and beyond it: counted once
    ev("async", "collective-permute-start.1", 3.5, 4.5),
    ev("async", "copy-start.7", 1.0, 9.0),
    # program B: 6..8; its last op sticks out of the marker on no side
    ev("module", "jit_b(12)", 6.0, 8.0),
    ev("op", "fusion.9", 6.0, 8.0),
    # out of the window altogether, and one that the window cuts
    ev("module", "jit_b(12)", 9.5, 12.0),
    ev("op", "fusion.9", 9.5, 12.0),
    ev("op", "fusion.9", 20.0, 21.0),
    # a second device, busy all through
    ev("op", "fusion.1", 0.0, 10.0, dev=1),
]


def test_busy_idle_self_time_programs_and_gaps():
    r = tr.reduce([dict(e) for e in EVENTS])
    assert r["window_s"] == 10.0
    d0 = tr.first_device(r)
    # busy: [1,4] + [6,8] + [9.5,10] = 5.5 of 10
    assert math.isclose(d0["busy_s"], 5.5)
    assert math.isclose(tr.busy_s(r), (5.5 + 10.0) / 2)
    ops = {(o["name"], o["t0"]): o for o in d0["ops"]}
    assert math.isclose(ops[("while.1", 1.0)]["self"], 0.5)
    assert math.isclose(ops[("fusion.1", 1.0)]["self"], 1.0)
    assert ops[("fusion.1", 1.0)]["program"] == "jit_a#11"
    assert ops[("fusion.9", 6.0)]["program"] == "jit_b#12"
    assert math.isclose(ops[("fusion.9", 9.5)]["self"], 0.5)   # cut
    gaps = [(g["before"], g["after"], round(g["t1"] - g["t0"], 6))
            for g in d0["gaps"]]
    assert gaps == [("jit_a#11", "jit_b#12", 2.0),
                    ("jit_b#12", "jit_b#12", 1.5)]
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["jit_b#12:fusion.9", 2.5]
    assert b["idle_gaps"][0][0].startswith("jit_a#11>jit_b#12 n=1")
    assert math.isclose(b["idle_gaps"][0][1], 2.0)


def test_readers_on_the_hand_written_list():
    import run as grid_run
    r = tr.reduce([dict(e) for e in EVENTS])
    obs = {"reduced": r, "traced_steps": 2,
           "config": {"programs": {"decode": "jit_b", "prefill": "jit_a"}}}

    def read(name):
        return grid_run.load_module("readers", name).read(obs)
    assert math.isclose(read("device_idle_share.train"), 45.0)
    assert math.isclose(read("device_idle_share.serve"), 22.5)
    assert math.isclose(read("train_step_dev_ms"), 2750.0)
    assert math.isclose(read("collective_share"), 100 * 1.5 / 5.5)
    assert math.isclose(read("decode_step_dev_ms"), 1250.0)  # 2.0, 0.5
    assert math.isclose(read("prefill_dev_share"), 100 * 3.0 / 5.5)
    # nothing to read -> nothing reported
    assert grid_run.load_module("readers", "train_mfu").read(
        {"device": {"peaks": None}}) is None
    assert read("shed_share") is None


def test_first_step_on_a_trimmed_real_trace():
    """0.4 s of a chat-cell trace from the v5e (PR 24), cut to the two
    device lines and the marker, instruction text cut to its name."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    events = tr.events_from_xplane(
        os.path.join(here, "chat_trace_trimmed.xplane.pb"))
    kinds = {e["kind"] for e in events}
    assert kinds == {"marker", "module", "op"}    # async was cut away
    assert all(not e["name"].startswith("%") and " = " not in e["name"]
               for e in events)
    r = tr.reduce(events)
    d = tr.first_device(r)
    assert math.isclose(r["window_s"], 0.4)
    assert 0.38 < d["busy_s"] < 0.4
    decode = tr.most_run(d["modules"], "jit__unknown")
    assert decode == "jit__unknown#6891"
    durs = sorted(m["t1"] - m["t0"] for m in d["modules"]
                  if tr.program_of(m["name"]) == decode)
    assert 0.080 < durs[len(durs) // 2] < 0.090      # an 85 ms step
    assert all(op["program"] for op in d["ops"])
    assert math.isclose(sum(op["self"] for op in d["ops"]), d["busy_s"],
                        rel_tol=1e-3)
    b = tr.breakdown(r)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][1] > 0.04
    assert any(name.startswith("inside ") for name, _ in b["idle_gaps"])


def test_no_marker_falls_back_to_the_extent():
    r = tr.reduce([dict(e) for e in EVENTS if e["kind"] != "marker"
                   and e["dev"] == 0])
    assert math.isclose(r["window_s"], 20.0)
    assert tr.program_of("jit__lambda_(1234567)") == "jit__lambda_#4567"
    assert tr.short_name("%fusion.3 = bf16[8]{0} fusion(%p)") == "fusion.3"
