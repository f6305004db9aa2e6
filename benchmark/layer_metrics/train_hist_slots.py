"""Histogram operand slots a row per pass: the `hist_slots` attribute of
the sweep's `sweep:bin` span (`parallel/sweep.py`: `max_bins` a wide
column, 2 an indicator column), as the typed driver records it under a
pass's `counters`; averaged over the window's passes. Nothing to read
from a program (or a driver) without the counter."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes or not all(
            "hist_slots" in p.get("counters", {}) for p in passes):
        return None
    return sum(p["counters"]["hist_slots"] for p in passes) / len(passes)
