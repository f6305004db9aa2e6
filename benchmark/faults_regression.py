"""Faults planted under a numeric-target pass, beside `faults.py` and
`faults_typed.py`: `correct` has to come out false with each. They patch
the PROGRAM's modules in this process.

- `half_rows_forest`, `half_rows_boost`: the sweep's forest fold fits,
  or its boosted chains' fold fits, see the first half of their rows
  only (`faults.py`'s `half_batch.sweep_trees`, one family at a time:
  each family's fold is held by numbers of its own);
- `round_short`: every boosted chain of the sweep runs one round short
  of the configuration's;
- `start_at_zero`: a boosted chain starts at 0 and not at the weighted
  mean of the target, in the sweep and in the refit;
- `level_folded`: every pivot's fit loses the last level of its
  vocabulary, whose cells then count as OTHER (`faults_typed.py`'s).
"""

from __future__ import annotations

REGRESSION = ("half_rows_forest", "half_rows_boost", "round_short",
              "start_at_zero", "level_folded")
_PLANTED = []       # one fault a process: the patches do not come off


def plant(fault: str) -> None:
    if fault not in REGRESSION:
        raise ValueError(f"no numeric-target fault {fault!r} (have "
                         + ", ".join(REGRESSION) + ")")
    if _PLANTED:
        if _PLANTED != [fault]:
            raise RuntimeError(f"{_PLANTED[0]!r} is planted already")
        return
    _PLANTED.append(fault)

    if fault == "level_folded":
        import faults_typed
        faults_typed.plant("level_folded")
        return

    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.parallel import sweep

    if fault.startswith("half_rows_"):
        import faults
        site = "_sweep_forest" if fault == "half_rows_forest" \
            else "_sweep_gbt"
        real_sweep = getattr(sweep, site)

        def family_sweep(est, grids, X, y, W, V, *a, **kw):
            return real_sweep(est, grids, X, y,
                              faults._first_half(jnp.asarray(W)), V, *a, **kw)
        setattr(sweep, site, family_sweep)
        return

    if fault == "round_short":
        real = sweep._static_gbt

        def static_gbt(est, g):
            st = real(est, g)
            return (st[0] - 1,) + st[1:]
        sweep._static_gbt = static_gbt
        return

    # start_at_zero: the refit looks the rule up in `models/trees.py`,
    # the sweep under the name it imported
    trees.gbt_base_score = lambda y, w, objective: jnp.float32(0.0)
    sweep.gbt_base_score = trees.gbt_base_score
