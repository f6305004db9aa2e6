"""The winner's refit on the whole prepared training set: the
`selector:refit` span (`selector/model_selector.py`), its compile
included, summed within a pass, averaged over the window's passes."""


def read(obs):
    passes = obs["window"].get("passes") or []
    found = [[d for name, d in p["spans"] if name == "selector:refit"]
             for p in passes]
    if not any(found):
        return None
    return sum(map(sum, found)) / len(found)
