"""What the window's first pass costs over the later ones: its
`workflow:train` span wall less the median of the later passes'. The
span and not the pass's host-clock wall: the profiler's start and stop
lie outside the span (`train_unspanned_s`). `train_pass_s` and the
other per-layer metrics average the window, so this is the part of them
that depends on how many passes the window held. None under two passes,
and from a program without `obs.trace.train_passes()`."""

import pass_spans


def read(obs):
    return pass_spans.first_pass_extra(obs)
