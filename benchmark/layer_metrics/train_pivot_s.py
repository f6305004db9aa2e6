"""The pivot's host passes over the text cells per pass: the `pivot:fit`
and `pivot:encode` span walls (`ops/categorical.py`), summed within a
pass, averaged over the window's passes. Nothing to read from a program
without those spans, or from a table with no pivoted column."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    per_pass = [[d for name, d in p["spans"]
                 if name in ("pivot:fit", "pivot:encode")] for p in passes]
    if not all(per_pass):
        return None
    return sum(sum(ds) for ds in per_pass) / len(per_pass)
