"""The pass's least possible chip time over its wall, in percent: the
larger of operations/peak and bytes/peak, operations and bytes counted
by `benchmark/work.py` from the configuration's shapes and grid, wall
the window's mean pass: the whole pass's share of the chip's peak, so it
sits with the entry's layer and follows everything that lengthens a pass
(`train_busy_mfu_pct` divides by the device's busy seconds instead). A
share of the chip's peak: read on the chip only."""
import work


def read(obs):
    passes, peaks = obs["window"].get("passes"), obs.get("peaks")
    if not passes or not peaks:
        return None
    least, bound = work.least_seconds(
        work.train_pass(obs["config"], obs["window"]["rows"]), peaks)
    wall = sum(p["wall_s"] for p in passes) / len(passes)
    print(f"[bench] train_mfu_pct: least {least:.4f}s of {wall:.2f}s, "
          f"bound by {bound}", flush=True)
    return 100.0 * least / wall
