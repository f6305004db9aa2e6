"""Boosting rounds a pass's sweep ran: over the pass's
`sweep:dispatch:gbt` spans, the `rounds` attribute of each times its
`pairs` (the dispatch's real (configuration, fold) pairs: what a padded
tail repeats is not counted), as the numeric-target driver records it
under a pass's `counters` (`boost_rounds`); averaged over the window's
passes. A grid of g configurations, f folds and r rounds reads g·f·r.
Nothing to read from a program (or a driver) without the attributes."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes or not all(
            "boost_rounds" in p.get("counters", {}) for p in passes):
        return None
    return sum(p["counters"]["boost_rounds"] for p in passes) / len(passes)
