"""The sweep's softmax rounds: the least possible seconds of their
class-histogram products on the chip as a share of the WALL of the
rounds' dispatches, in percent. Not the products' roofline: a dispatch's
wall holds the whole round (the K-wide routing, the softmax and
gradients, the leaf sums) and the host's dispatch besides, so a gain in
any of them raises this share.

The least seconds are counted from the algorithm's shapes
(`work_softmax.class_hist_round`: each level of each class's tree as a
(nodes, n) @ (n, slots) product for the gradient and one for the
hessian) for every round the sweep ran: folds × configurations chains
of `n_estimators` rounds (the grid varies no shape), each over the n
training rows the chains carry. The wall is the pass's
`sweep:dispatch:gbt` spans: a dispatch runs the rounds program and
waits for it (`block_until_ready`), so it holds every operation of the
rounds, never a part of them. Mean over the window's passes; None where
a pass has no such span or the configuration no boosted family.
"""
import work_softmax


def read(obs):
    passes = obs["window"].get("passes")
    peaks = obs.get("peaks")
    if not passes or not peaks:
        return None
    walls = [sum(s for name, s in p["spans"] if name == "sweep:dispatch:gbt")
             for p in passes]
    if not all(walls):
        return None
    cfg = obs["config"]
    spec = cfg["selector"]
    fam = next((f for f in spec["families"]
                if "n_estimators" in f["params"]), None)
    if fam is None:
        return None
    k = int(cfg["schema"]["classes"])
    d = int(cfg["schema"]["columns"]["count"])
    n = int(round(int(obs["window"]["rows"])
                  * (1 - spec["splitter"]["reserve_test_fraction"])))
    p = fam["params"]
    rounds = (int(spec["validator"].get("folds", 1)) * len(fam["grid"])
              * int(p["n_estimators"]))
    one = work_softmax.class_hist_round(
        n, k, int(p["max_depth"]), d * int(p.get("max_bins", 32)))
    least = rounds * max(one["ops"] / peaks["flops_per_s"],
                         one["bytes"] / peaks["hbm_bytes_per_s"])
    wall = sum(walls) / len(walls)
    print(f"[bench] train_softmax_hist_dispatch_pct: least {least:.4f}s of "
          f"{wall:.3f}s of the softmax rounds' dispatches", flush=True)
    return 100.0 * least / wall
