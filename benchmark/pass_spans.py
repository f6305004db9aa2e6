"""The window's passes with their spans' attributes, for the readers a
`(name, seconds)` pair is not enough for: the program's
`obs.trace.train_passes()` groups its finished spans by the
`workflow:train` span they descend from, and the window's passes are the
last ones (the warm-up passes precede them; a run holds far fewer spans
than the tracer's ring). Called in the run's own process, after the
window. A program from before `train_passes()` gives nothing."""

import statistics


def window_passes(obs):
    """[{"root": Span, "spans": [Span, ...]}, ...] of the window's
    completed passes, oldest first, or None."""
    n = len(obs["window"].get("passes") or [])
    if not n:
        return None
    try:
        from transmogrifai_tpu.obs.trace import train_passes
    except ImportError:
        return None
    done = [p for p in train_passes() if p["root"].error is None]
    return done[-n:] if len(done) >= n else None


def transfer_mean(obs, prefix, attribute=None):
    """Over the window's passes, the mean of a pass's sum over its
    `<prefix>*` spans of `attribute`, or of their walls without one
    (thread-seconds: the family threads' spans add up). None where no
    pass holds such a span."""
    passes = window_passes(obs)
    if not passes:
        return None
    per_pass = [[sp.duration_s if attribute is None
                 else sp.attributes.get(attribute, 0)
                 for sp in p["spans"] if sp.name.startswith(prefix)]
                for p in passes]
    if not any(per_pass):
        return None
    return sum(map(sum, per_pass)) / len(per_pass)


def first_pass_extra(obs):
    """The `workflow:train` wall of the window's first pass less the
    median of the later passes'; None under two passes."""
    passes = window_passes(obs)
    if not passes or len(passes) < 2:
        return None
    walls = [p["root"].duration_s for p in passes]
    return walls[0] - statistics.median(walls[1:])
