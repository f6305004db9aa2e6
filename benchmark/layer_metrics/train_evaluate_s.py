"""Scoring the refitted winner on the training rows and the holdout and
computing the summary's metrics on the host: the `selector:evaluate`
span (`selector/model_selector.py`), summed within a pass, averaged
over the window's passes."""


def read(obs):
    passes = obs["window"].get("passes") or []
    found = [[d for name, d in p["spans"] if name == "selector:evaluate"]
             for p in passes]
    if not any(found):
        return None
    return sum(map(sum, found)) / len(found)
