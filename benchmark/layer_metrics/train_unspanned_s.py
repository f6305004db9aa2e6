"""Main-thread seconds of a pass that no leaf span names: the pass's
wall minus its `stage:fit:*` / `stage:transform:*` spans and
`workflow:materialize` (what is left lies inside `workflow:train`
between the stages, or before it, in the caller's graph building), plus
the selector's own `stage:fit:*` minus the `selector:*` phases under
it. The window's first pass is the one the profiler records, and its
host-clock wall holds the profiler's own start and stop: the traced
window (the `bench:` annotation, `trace_reduce.py`) stands in for it.
Averaged over the window's passes; nothing to read from a program
without those spans."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    traced_s = (obs.get("trace") or {}).get("window_s")
    per_pass = []
    for i, p in enumerate(passes):
        spans = p["spans"]
        wall = min(p["wall_s"], traced_s) if i == 0 and traced_s \
            else p["wall_s"]
        if not any(name == "workflow:materialize" for name, _ in spans) \
                or not any(name.startswith("selector:") for name, _ in spans):
            return None
        stages = sum(d for name, d in spans if name.startswith(
            ("stage:fit:", "stage:transform:")))
        materialize = sum(d for name, d in spans
                          if name == "workflow:materialize")
        selector_fit = sum(d for name, d in spans if name.startswith(
            "stage:fit:") and "ModelSelector" in name)
        phases = sum(d for name, d in spans if name.startswith("selector:"))
        per_pass.append((wall - stages - materialize)
                        + (selector_fit - phases))
    return sum(per_pass) / len(per_pass)
