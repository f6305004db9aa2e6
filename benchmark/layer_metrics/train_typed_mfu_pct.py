"""A typed table's pass: its least possible chip time over its wall, in
percent — `train_mfu_pct` with the work counted by
`benchmark/work_typed.py` (the matrix the checker keeps, not the raw
column count). A share of the chip's peak: read on the chip only."""
import work_typed


def read(obs):
    passes = obs["window"].get("passes")
    least = work_typed.least_seconds(
        obs["config"], obs["window"]["rows"], obs.get("peaks"))
    if not passes or least is None:
        return None
    wall = sum(p["wall_s"] for p in passes) / len(passes)
    print(f"[bench] train_typed_mfu_pct: least {least[0]:.4f}s of "
          f"{wall:.2f}s, bound by {least[1]}", flush=True)
    return 100.0 * least[0] / wall
