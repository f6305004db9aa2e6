"""The raw columns' materialisation per pass: the `workflow:materialize`
span wall (`workflow/workflow.py`: every raw feature's
`Column.from_values` of the table's storage, a text column's
factorization included), summed within a pass, averaged over the
window's passes. Nothing to read from a program without the span."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    per_pass = [[d for name, d in p["spans"]
                 if name == "workflow:materialize"] for p in passes]
    if not all(per_pass):
        return None
    return sum(sum(ds) for ds in per_pass) / len(per_pass)
