"""Forward passes a slot spends on a block: the counter
``serve_block_passes_total`` (slot-passes of the step program, counted
on the device over the slots that ran and carried out beside the
tokens) over ``serve_blocks_committed_total`` between the two scrapes.
``denoising_steps + 1`` for full blocks under the static schedule (5.0
at 4 steps); a first block opened by a prompt's remainder takes fewer.
Nothing where the program has no such counters."""


def read(obs):
    s0, s1 = obs.get("scrape0"), obs.get("scrape1")
    passes, blocks = "serve_block_passes_total", \
        "serve_blocks_committed_total"
    if not s0 or not s1 or passes not in s1 or blocks not in s1:
        return None
    done = s1[blocks] - s0.get(blocks, 0.0)
    return (s1[passes] - s0.get(passes, 0.0)) / done if done else None
