"""Columns out of `transmogrify()` per pass: the `encoded_width`
attribute of the checker's `sanity:decide` span, as the typed driver
records it under a pass's `counters`; averaged over the window's passes.
Nothing to read from a program (or a driver) without the counter."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes or not all(
            "encoded_width" in p.get("counters", {}) for p in passes):
        return None
    return sum(p["counters"]["encoded_width"] for p in passes) / len(passes)
