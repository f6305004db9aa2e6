"""Passes over the histogram operand a tree level: the `hist_reads`
attribute of the sweep's `sweep:bin` span (`parallel/sweep.py`; one a
value column and one for the weights in the per-column form, 1 where a
classifier's K class histograms are one composite histogram), as the
many-label driver records it under a pass's `counters`; averaged over
the window's passes. Nothing to read from a program (or a driver)
without the counter."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes or not all(
            "hist_reads" in p.get("counters", {}) for p in passes):
        return None
    return sum(p["counters"]["hist_reads"] for p in passes) / len(passes)
