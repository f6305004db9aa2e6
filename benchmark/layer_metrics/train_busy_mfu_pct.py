"""The pass's least possible chip time over the seconds the device was
BUSY in the traced pass, in percent: how near the kernels that did run
come to the chip's peak, whatever the host made the chip wait
(`train_mfu_pct` is the same least time over the whole pass wall).
Least time from `benchmark/work.py` (the larger of operations/peak and
bytes/peak); busy seconds from the profiler trace
(`benchmark/trace_reduce.py`). Nothing to read without a device trace."""
import work


def read(obs):
    t, peaks = obs.get("trace"), obs.get("peaks")
    if not t or not t.get("n_ops") or not t.get("busy_s") or not peaks:
        return None
    least, bound = work.least_seconds(
        work.train_pass(obs["config"], obs["window"]["rows"]), peaks)
    print(f"[bench] train_busy_mfu_pct: least {least:.4f}s of "
          f"{t['busy_s']:.2f}s busy, bound by {bound}", flush=True)
    return 100.0 * least / t["busy_s"]
