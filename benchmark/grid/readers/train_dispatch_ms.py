"""Host time of one train-step dispatch: the median duration of the
window's ``train.step_dispatch`` spans, the last ``attempted`` of them
in the program's own ring (``telemetry.trace_events()``; the readers
run in the training process). The gap between two steps on the device
is this plus the loss read-back."""
from statistics import median

SPAN = "train.step_dispatch"


def read(obs):
    events = obs.get("trace_events")
    if events is None:
        from mxtpu import telemetry
        events = telemetry.trace_events()
    durs = [e["dur"] for e in events
            if e.get("name") == SPAN and e.get("ph") == "X"]
    n = int(obs.get("attempted") or 0)
    if not durs or n <= 0:
        return None
    return 1e-3 * median(durs[-n:])
