"""Seconds a pass spent reading device arrays to the host: the walls of
its `pull:*` spans (`obs/trace.py` `pull`, every site by name: the
checker's matrix, a vectorizer's fills, the sweep's fold metrics, the
evaluator's predictions, a fitted model's tables), summed over the
threads within a pass, averaged over the window's passes. Each holds
the wait for the producer (`train_pull_wait_s`) and the read itself.
Nothing to read from a program without those spans."""

import pass_spans


def read(obs):
    return pass_spans.transfer_mean(obs, "pull:")
