"""A softmax-boosting pass: its least possible chip time over the seconds
the device was BUSY in the traced pass, in percent —
`train_busy_mfu_pct` with the work counted by `benchmark/work_softmax.py`.
Nothing to read without a device trace."""
import work_softmax


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("n_ops") or not t.get("busy_s"):
        return None
    least = work_softmax.least_seconds(
        obs["config"], obs["window"]["rows"], obs.get("peaks"))
    if least is None:
        return None
    print(f"[bench] train_softmax_busy_mfu_pct: least {least[0]:.4f}s of "
          f"{t['busy_s']:.2f}s busy, bound by {least[1]}", flush=True)
    return 100.0 * least[0] / t["busy_s"]
