"""Backend compile inside a pass: the `compile:*` span durations
(`utils/compile_cache.py` backdates one under the program span whose
thread asked XLA for the compile), summed within a pass — thread-seconds,
families compile side by side, so this can exceed the pass's wall —
and averaged over the window's passes. A program that opens
`workflow:train` records every compile as a span, so a pass of it
without one reads 0; nothing to read from a program with neither."""


def read(obs):
    passes = obs["window"].get("passes") or []
    if not any(name.startswith("compile:") or name == "workflow:train"
               for p in passes for name, _ in p["spans"]):
        return None
    return sum(d for p in passes for name, d in p["spans"]
               if name.startswith("compile:")) / len(passes)
