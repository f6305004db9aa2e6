"""A softmax-boosting pass: its least possible chip time over its wall,
in percent — `train_mfu_pct` with the work counted by
`benchmark/work_softmax.py` (K trees a round, each level two
accumulations a cell of every class, the softmax and gradient pass, the
leaf sums and the routing, the rounds one after another, 4·n·d·K
operations a multinomial FISTA iteration). A share of the chip's peak:
read on the chip only."""
import work_softmax


def read(obs):
    passes = obs["window"].get("passes")
    least = work_softmax.least_seconds(
        obs["config"], obs["window"]["rows"], obs.get("peaks"))
    if not passes or least is None:
        return None
    wall = sum(p["wall_s"] for p in passes) / len(passes)
    print(f"[bench] train_softmax_mfu_pct: least {least[0]:.4f}s of "
          f"{wall:.2f}s, bound by {least[1]}", flush=True)
    return 100.0 * least[0] / wall
