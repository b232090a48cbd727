"""The longest stretch of the window in which no response was
completed, in ms. At some thousands of responses a second the gaps are
a few ms, or one batch period where the device releases a batch at a
time; a pause of the served path stands out as hundreds. It is what the
99th percentile rests on, read more steadily: one number per pause, not
one per request caught in it."""

import numpy as np


def read(obs):
    records, (lo, hi) = obs["records"], obs["window_ns"]
    done = records["done_ns"][records["outcome"] == 0]
    done = np.sort(done[(done >= lo) & (done < hi)])
    if len(done) < 2:
        return None
    return float(np.diff(np.concatenate(([lo], done, [hi]))).max() / 1e6)
