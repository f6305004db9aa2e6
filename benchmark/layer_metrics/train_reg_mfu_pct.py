"""A numeric-target typed table's pass: its least possible chip time over
its wall, in percent — `train_mfu_pct` with the work counted by
`benchmark/work_regression.py` (the matrix the checker keeps, two
accumulations a cell of a tree level, the boosted chain's rounds one
after another, 4·n·d operations a least-squares iteration). A share of
the chip's peak: read on the chip only."""
import work_regression


def read(obs):
    passes = obs["window"].get("passes")
    least = work_regression.least_seconds(
        obs["config"], obs["window"]["rows"], obs.get("peaks"))
    if not passes or least is None:
        return None
    wall = sum(p["wall_s"] for p in passes) / len(passes)
    print(f"[bench] train_reg_mfu_pct: least {least[0]:.4f}s of "
          f"{wall:.2f}s, bound by {least[1]}", flush=True)
    return 100.0 * least[0] / wall
