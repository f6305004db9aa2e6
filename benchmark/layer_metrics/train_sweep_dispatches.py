"""`SWEEP_STATS.dispatches` per pass (`parallel/sweep.py`): timed host
dispatches of the tree families' sweep programs."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    return sum(p["sweep_dispatches"] for p in passes) / len(passes)
