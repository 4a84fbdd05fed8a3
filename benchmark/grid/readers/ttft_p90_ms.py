"""The tail of the time to first token: the 90th percentile over the
requests whose first token arrived in the window (about 113 of them in
51 s, so 11 lie beyond it). It lands on one side or the other of a
decode step's boundary and swings by 4-5% between runs of one code, so
it stands here and the median is the end-to-end metric."""


def read(obs):
    return obs.get("window", {}).get("ttft_p90_ms")
