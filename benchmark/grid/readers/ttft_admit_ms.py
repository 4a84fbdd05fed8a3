"""Time to first token, part 2 of 4: picked to admission program
dispatched (page plan, copy-page, key, arrays, the dispatch call).
Mean over the requests whose first token left the engine between the
two scrapes (``serve_ttft_admit_ms``)."""


def read(obs):
    from program_reads import hist_mean
    return hist_mean(obs, "serve_ttft_admit_ms")
