"""`SWEEP_STATS.dispatch_s` per pass: host wall inside timed sweep
dispatches, SUMMED over the family threads — families overlap on a
thread pool, so this can exceed the pass's own wall."""


def read(obs):
    passes = obs["window"].get("passes")
    if not passes:
        return None
    return sum(p["sweep_dispatch_s"] for p in passes) / len(passes)
