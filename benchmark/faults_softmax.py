"""Faults planted under a softmax-boosting pass: `correct` has to come out
false with each. They patch the PROGRAM's modules in this process.

- `classes_one_short`: the boosted family's softmax runs over K − 1
  classes (the top label has no margin), in the sweep and in the refit;
- `hessian_constant`: a softmax round's hessians are the row weights
  alone, H = w, not max(p(1 − p), 1e-6) w;
- `round_short`: every boosted chain of the sweep runs one round short
  of the configuration's (`faults_regression.py`'s);
- `half_rows_boost`: the sweep's boosted fold fits see the first half of
  their rows only (`faults_regression.py`'s);
- `class_trees_swapped`: in every softmax round the trees of classes 0
  and 1 are exchanged: class 0's margin moves by the tree grown from
  class 1's gradients and the other way round.
"""

from __future__ import annotations

SOFTMAX = ("classes_one_short", "hessian_constant", "round_short",
           "half_rows_boost", "class_trees_swapped")
_PLANTED = []       # one fault a process: the patches do not come off


def plant(fault: str) -> None:
    if fault not in SOFTMAX:
        raise ValueError(f"no softmax fault {fault!r} (have "
                         + ", ".join(SOFTMAX) + ")")
    if _PLANTED:
        if _PLANTED != [fault]:
            raise RuntimeError(f"{_PLANTED[0]!r} is planted already")
        return
    _PLANTED.append(fault)

    if fault in ("round_short", "half_rows_boost"):
        import faults_regression
        faults_regression.plant(fault)
        return

    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.parallel import sweep

    if fault == "classes_one_short":
        real = trees.n_classes_of

        def n_classes_of(est, y, ctx=None):
            k = real(est, y, ctx)
            return k - 1 if isinstance(est, trees.OpGBTClassifier) else k
        trees.n_classes_of = n_classes_of
        sweep.n_classes_of = n_classes_of
        return

    real_grads = trees.gbt_grad_hess

    def gbt_grad_hess(margin, y, w, objective):
        G, H = real_grads(margin, y, w, objective)
        if objective != "softmax":
            return G, H
        if fault == "hessian_constant":
            return G, jnp.broadcast_to(w[:, None], H.shape)
        swap = jnp.arange(G.shape[1]).at[0].set(1).at[1].set(0)
        return G[:, swap], H[:, swap]
    trees.gbt_grad_hess = gbt_grad_hess
