"""Seconds the boosted family's thread held the device inside its round
dispatches, compile taken out: the `sweep:dispatch:gbt` span durations
(`parallel/sweep.py` `_sweep_gbt`'s round-chunked host loop) minus the
`compile:sweep:dispatch:gbt*` spans nested in them, summed within a pass
(thread-seconds), averaged over the window's passes. Execution plus the
wait behind the other families' programs. Nothing to read from a pass
with no boosted dispatch."""


def read(obs):
    passes = obs["window"].get("passes") or []
    held = [[d for name, d in p["spans"] if name == "sweep:dispatch:gbt"]
            for p in passes]
    if not any(held):
        return None
    compiling = sum(d for p in passes for name, d in p["spans"]
                    if name.startswith("compile:sweep:dispatch:gbt"))
    return (sum(map(sum, held)) - compiling) / len(held)
