"""Time to first token, part 3 of 4: admission program dispatched to
first token on the host (the decode step in flight, the prefill, and
the decode step the first token rides behind). Mean over the requests
whose first token left the engine between the two scrapes
(``serve_ttft_first_wait_ms``)."""


def read(obs):
    from program_reads import hist_mean
    return hist_mean(obs, "serve_ttft_first_wait_ms")
