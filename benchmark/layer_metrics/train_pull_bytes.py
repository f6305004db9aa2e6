"""Bytes a pass read from the device to the host: the `bytes` attribute
of its `pull:*` spans (`obs/trace.py` `pull`: the device leaves'
`nbytes`), summed within a pass, averaged over the window's passes.
Nothing to read from a program without those spans."""

import pass_spans


def read(obs):
    return pass_spans.transfer_mean(obs, "pull:", "bytes")
