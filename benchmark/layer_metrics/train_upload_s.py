"""Host seconds a pass spent in calls that copy host arrays to the
device: the walls of its `upload:*` spans (`obs/trace.py` `upload`,
`uploading`: a stage's raw columns, the pivot's ids, the checker's
sample, the selector's label and row index, the sweep's fold masks),
summed over the threads within a pass, averaged over the window's
passes. Nothing is blocked on after an upload: the transfer's tail
shows as the next pull's wait (`train_pull_wait_s`). Nothing to read
from a program without those spans."""

import pass_spans


def read(obs):
    return pass_spans.transfer_mean(obs, "upload:")
