"""Seconds the family threads waited on the device inside sweep
dispatches, compile taken out: the `sweep:dispatch:*` span durations
(`parallel/sweep.py`, every family, the logistic block included) minus
the `compile:sweep:dispatch:*` spans nested in them, summed over the
threads within a pass, averaged over the window's passes. What is left
is real execution plus the wait behind another family's programs."""


def read(obs):
    passes = obs["window"].get("passes") or []
    held = [[d for name, d in p["spans"]
             if name.startswith("sweep:dispatch:")] for p in passes]
    if not any(held):
        return None
    compiling = sum(d for p in passes for name, d in p["spans"]
                    if name.startswith("compile:sweep:dispatch:"))
    return (sum(map(sum, held)) - compiling) / len(held)
