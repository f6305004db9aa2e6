"""Faults planted under a typed table's feature stages, beside
`faults.py` (whose faults sit under the fits): `correct` has to come out
false with each. They patch the PROGRAM's classes in this process.

- `level_folded`: every pivot's fit loses the last level of its
  vocabulary, whose cells then count as OTHER;
- `null_as_level`: a pivot encodes a missing cell as its first level;
- `dropped_kept`: the checker keeps the first column its rules drop.
"""

from __future__ import annotations

TYPED = ("level_folded", "null_as_level", "dropped_kept")
_PLANTED = []       # one fault a process: the patches do not come off


def plant(fault: str) -> None:
    if fault not in TYPED:
        raise ValueError(f"no typed-table fault {fault!r} (have "
                         + ", ".join(TYPED) + ")")
    if _PLANTED:
        if _PLANTED != [fault]:
            raise RuntimeError(f"{_PLANTED[0]!r} is planted already")
        return
    _PLANTED.append(fault)
    from transmogrifai_tpu.automl import sanity_checker
    from transmogrifai_tpu.ops import categorical

    if fault == "level_folded":
        real_fit = categorical.OneHotVectorizer.fit_model

        def fit_model(self, cols, ctx):
            model = real_fit(self, cols, ctx)
            return categorical.OneHotModel(
                [v[:-1] for v in model.vocabs], model.track_nulls)
        categorical.OneHotVectorizer.fit_model = fit_model

    elif fault == "null_as_level":
        real_prepare = categorical.OneHotModel.host_prepare

        def host_prepare(self, cols):
            out = real_prepare(self, cols)
            for ids, vocab in zip(out, self.vocabs):
                ids[ids == len(vocab) + 1] = 0
            return out
        categorical.OneHotModel.host_prepare = host_prepare

    else:
        real_fit = sanity_checker.SanityChecker.fit_model

        def fit_model(self, cols, ctx):
            model = real_fit(self, cols, ctx)
            dropped = model.summary["dropped"]
            if not dropped:
                return model
            kept = sorted(model.indices + dropped[:1])
            meta = cols[1].meta
            return sanity_checker.SanityCheckerModel(
                kept, meta=None if meta is None else meta.select(kept),
                summary=model.summary)
        sanity_checker.SanityChecker.fit_model = fit_model
