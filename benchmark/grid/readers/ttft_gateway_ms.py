"""Time to first token, part 4 of 4: what the gateway and the front
door add around the engine. The gateway's mean TTFT between the two
scrapes (``gateway_ttft_ms``: its submit to its first ``on_token``)
less the engine's three parts, so the four add to that mean by
construction."""
PARTS = ("serve_ttft_queue_ms", "serve_ttft_admit_ms",
         "serve_ttft_first_wait_ms")


def read(obs):
    from program_reads import hist_mean
    total = hist_mean(obs, "gateway_ttft_ms")
    parts = [hist_mean(obs, name) for name in PARTS]
    if total is None or any(p is None for p in parts):
        return None
    return total - sum(parts)
