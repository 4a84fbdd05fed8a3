"""What a prefill chunk of the retention family takes from the requests
that are running, over the WHOLE window: the window's seconds less its
decode steps at the traced step's device time, over the chunks
dispatched in it (``span_serve_prefill_ms``'s count: the engine enters
that span once a chunk). The device runs a chunk between two decode
steps and is idle 0.05-0.6% of such a window, so what the steps leave
of it is the chunks': the mean device time of one (a prompt's last
chunk, which also seats the state and samples, and the chunks before
it, as they fell), with whatever else stalled the steps (v5e, PR 33:
100.4-102.4 where the trace times the last chunk at 98.85). The traced
seconds alone see too few chunks to time one (none in about one window
of seven: the accepted ``prefill_chunk_dev_ms`` has nothing to read
there); steps and chunks are the two scrapes' exact counts, the step's
time the median run of the decode program in the trace
(``decode_step_dev_ms``). Nothing without a trace, the counters, or a
chunk in the window."""
from statistics import median


def read(obs):
    from program_reads import hist_sum
    from trace_reduce import first_device, most_run, program_of
    d = first_device(obs["reduced"]) if "reduced" in obs else None
    pat = obs["config"].get("programs", {}).get("decode")
    chunks = hist_sum(obs, "span_serve_prefill_ms", "_count")
    if d is None or not pat or not chunks:
        return None
    decode = most_run(d["modules"], pat)
    durs = [m["t1"] - m["t0"] for m in d["modules"]
            if program_of(m["name"]) == decode]
    steps = obs["scrape1"].get("serve_steps_total", 0.0) \
        - obs["scrape0"].get("serve_steps_total", 0.0)
    if not durs or steps <= 0:
        return None
    return 1e3 * (obs["seconds"] - steps * median(durs)) / chunks
