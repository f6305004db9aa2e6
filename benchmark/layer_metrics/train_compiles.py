"""Compile requests inside the window that the persistent cache did not
answer (`jax.monitoring` events, counted by the harness). After a warm-up
pass this should read 0; anything else is a shape that changed between
passes."""


def read(obs):
    c = obs["window"].get("compiles")
    if c is None:
        return None
    return c["requests"] - c["cache_hits"]
