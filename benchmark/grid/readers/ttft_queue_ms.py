"""Time to first token, part 1 of 4: ``engine.submit`` to picked for
admission (waiting for a step boundary and a slot). Mean over the
requests whose first token left the engine between the two scrapes
(``serve_ttft_queue_ms``)."""


def read(obs):
    from program_reads import hist_mean
    return hist_mean(obs, "serve_ttft_queue_ms")
