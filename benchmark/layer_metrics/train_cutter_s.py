"""The label cutter's seconds per pass: the `cutter:prepare` span walls
(`selector/splitters.py` `DataCutter.prepare`, under `selector:prepare`),
summed within a pass, averaged over the window's passes. Nothing to read
from a program without the span."""


def read(obs):
    passes = obs["window"].get("passes") or []
    found = [[d for name, d in p["spans"] if name == "cutter:prepare"]
             for p in passes]
    if not any(found):
        return None
    return sum(map(sum, found)) / len(found)
