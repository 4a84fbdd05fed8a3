"""Idle gaps named by host span: the cutting of a gap at span
boundaries on spans written by hand, and the whole reduction on a
small recorded trace of the train step (``train_spans_trimmed.xplane.pb``,
kept beside the chat cell's)."""
import math
import os

import pytest

import span_reduce as sr
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "train_spans_trimmed.xplane.pb")


def span(name, t0, t1, thread="python3#7"):
    return {"name": name, "thread": thread, "t0": t0, "t1": t1}


SPANS = [
    # the loop thread: admit holds a prefill; then dispatch, readback
    span("serve.admit", 1.0, 4.0),
    span("serve.prefill", 2.0, 3.0),
    span("serve.decode_step", 4.5, 5.0),
    span("serve.readback", 5.0, 9.0),
    # another thread's span over everything: never the loop's
    span("gateway.request", 0.0, 10.0, thread="python3#9"),
]


def test_the_loop_thread_is_the_one_with_most_spans():
    assert sr.loop_thread(SPANS) == "python3#7"
    assert sr.loop_thread([]) is None


def test_a_gap_is_cut_at_span_boundaries_innermost_first():
    gaps = [{"t0": 1.5, "t1": 4.75, "before": "a", "after": "b"},
            {"t0": 9.5, "t1": 9.75, "before": "b", "after": "b"}]
    first, second = sr.name_gaps(SPANS, gaps)
    # 1.5-2 admit, 2-3 prefill (innermost), 3-4 admit, 4-4.5 nothing,
    # 4.5-4.75 decode_step
    assert first["parts"] == pytest.approx({
        "serve.admit": 1.5, "serve.prefill": 1.0, sr.NONE: 0.5,
        "serve.decode_step": 0.25})
    assert first["span"] == "serve.admit" and first["before"] == "a"
    assert second["parts"] == {sr.NONE: 0.25} and second["span"] == sr.NONE
    rows = sr.table([first, second])
    assert rows[0][:3] == ["serve.admit", 1.5, 1]
    assert math.isclose(rows[0][3], 3250.0)
    assert math.isclose(sum(r[1] for r in rows), 3.5)    # all idle time


def test_recorded_trace_holds_the_spans_inside_the_window():
    spans = sr.host_spans(RECORDED)
    names = {s["name"] for s in spans}
    assert "train.step_dispatch" in names, sorted(names)
    marker = next(e for e in tr.events_from_xplane(RECORDED)
                  if e["kind"] == "marker")
    mine = [s for s in spans if s["name"] == "train.step_dispatch"]
    assert len(mine) >= 3
    # on the device lines' clock: every dispatch span lies in the window
    assert all(marker["t0"] <= s["t0"] and s["t1"] <= marker["t1"]
               for s in mine)


def test_recorded_traces_gaps_between_steps_are_named():
    got = sr.gaps_by_span(RECORDED)
    between = [g for g in got["gaps"] if not g["inside"]]
    assert between and got["thread"] is not None
    # every gap between two steps is accounted for, span by span
    for g in between:
        assert math.isclose(sum(g["parts"].values()), g["t1"] - g["t0"],
                            rel_tol=1e-6)
    assert math.isclose(sum(r[1] for r in got["table"]), got["idle_s"],
                        rel_tol=1e-6)
    # the next step's dispatch is open over part of each such gap
    named = [g for g in between if "train.step_dispatch" in g["parts"]]
    assert len(named) >= len(between) - 1, [g["parts"] for g in between]
