"""Host seconds of set-up under ``setup.state_alloc``: the engine's
page pool and per-slot state (``init_paged_cache`` and its
book-keeping), or the train state's placement and optimizer state
(``init_state``). The device fills what was allocated behind it."""


def read(obs):
    from setup_reads import span_seconds
    return span_seconds(obs, "state_alloc")
