"""100 x (1 - union of device-operation intervals / traced window),
from the profiler trace of the window's first pass or call
(`benchmark/trace_reduce.py`). Nothing to read without a device trace."""


def read(obs):
    t = obs.get("trace")
    if not t or not t.get("n_ops") or t.get("idle_share") is None:
        return None
    return 100.0 * t["idle_share"]
