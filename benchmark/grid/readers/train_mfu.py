"""Model FLOP utilisation: the operations a token needs (forward and
backward, recomputation not counted; ``flops.train_flops_per_token``)
times the tokens per second per chip the window reached, over the
chip's bf16 peak."""


def read(obs):
    from flops import train_flops_per_token
    peaks = obs["device"].get("peaks")
    rate = obs.get("window", {}).get("train_tok_s")
    if not peaks or not rate:
        return None
    per_token = train_flops_per_token(obs["config"],
                                      obs["traffic"]["seq_len"])
    return 100.0 * per_token * rate / peaks["flops_bf16"]
