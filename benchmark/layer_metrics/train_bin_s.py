"""The sweep's binning of the training matrix per pass: the `sweep:bin`
span walls (`parallel/sweep.py` `_binned_cache`: the tree bin edges and
the binned matrix, one span a distinct `max_bins`, the other tree
families waiting on its lock meanwhile), summed within a pass, averaged
over the window's passes. Nothing to read from a program without the
span."""


def read(obs):
    passes = obs["window"].get("passes") or []
    found = [[d for name, d in p["spans"] if name == "sweep:bin"]
             for p in passes]
    if not any(found):
        return None
    return sum(map(sum, found)) / len(found)
