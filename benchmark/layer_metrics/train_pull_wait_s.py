"""The part of `train_pull_s` in which the producer had not finished:
the `wait_s` attribute of a pass's `pull:*` spans (`obs/trace.py`
`pull`: the seconds in `block_until_ready`, the chip computing or
still taking an upload the producer needs), summed over the threads
within a pass, averaged over the window's passes. What is left of
`train_pull_s` is the reads themselves, the chip idle unless another
thread holds it. Nothing to read from a program without those spans."""

import pass_spans


def read(obs):
    return pass_spans.transfer_mean(obs, "pull:", "wait_s")
