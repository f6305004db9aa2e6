"""Host feature stages per pass: the `stage:fit:*` and
`stage:transform:*` span walls (`obs/trace.py`) other than the model
selector's, summed within a pass, averaged over the window's passes."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    per_pass = [sum(d for name, d in p["spans"]
                    if name.startswith(("stage:fit:", "stage:transform:"))
                    and "ModelSelector" not in name) for p in passes]
    return sum(per_pass) / len(per_pass)
