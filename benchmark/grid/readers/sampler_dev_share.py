"""Self time of the decode program's operations under the scope
``sampler`` (``llama.sample_logits``) as a share of the program's self
time in the traced window. The decode program is found as
``decode_step_dev_ms`` finds it; its operations' scopes come from the
program's own catalog (``program_reads``)."""


def read(obs):
    from program_reads import decode_scope_share
    return decode_scope_share(obs, "sampler")
