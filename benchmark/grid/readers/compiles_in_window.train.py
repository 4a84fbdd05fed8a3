"""Programs JAX built or fetched between the window's first step and
its last, from the program's compile listener."""


def read(obs):
    return obs.get("compiles")
