"""The sanity checker's four phases per pass: the `sanity:moments`,
`sanity:corr`, `sanity:contingency` and `sanity:decide` span walls
(`automl/sanity_checker.py`), summed within a pass, averaged over the
window's passes. Nothing to read from a program without those spans."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    per_pass = [[d for name, d in p["spans"] if name.startswith("sanity:")]
                for p in passes]
    if not all(per_pass):
        return None
    return sum(sum(ds) for ds in per_pass) / len(per_pass)
