"""The share of the window the engine thread spends working, as
opposed to waiting for the device: the time inside its phase spans
``serve.sweep_pick``, ``serve.admit``, ``serve.decode_step`` (the
dispatch) and ``serve.emit`` between the two scrapes, over the window.
``serve.readback``, the fifth phase, is the wait. Leads
``device_idle_share``: the device starts to wait only when this nears
100%."""
PHASES = ("span_serve_sweep_pick_ms", "span_serve_admit_ms",
          "span_serve_decode_dispatch_ms", "span_serve_emit_ms")


def read(obs):
    from program_reads import hist_sum
    sums = [hist_sum(obs, name) for name in PHASES]
    if any(s is None for s in sums) or not obs.get("seconds"):
        return None
    return 100.0 * sum(sums) / (1e3 * obs["seconds"])
