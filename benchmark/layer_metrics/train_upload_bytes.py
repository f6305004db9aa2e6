"""Bytes a pass copied from host arrays to the device: the `bytes`
attribute of its `upload:*` spans (`obs/trace.py` `uploading`: the
numpy operands' `nbytes`), summed within a pass, averaged over the
window's passes. Nothing to read from a program without those spans."""

import pass_spans


def read(obs):
    return pass_spans.transfer_mean(obs, "upload:", "bytes")
