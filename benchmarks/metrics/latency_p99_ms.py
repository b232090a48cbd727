"""The far tail: 99th percentile of due time -> last byte, in ms, over
the requests of the window (its seconds before the traced part). A
request with no whole response counts at the longest wait. It rests on
pauses of the whole served path (`served_pause_max_ms`), one or two in
some windows and none in others, so from run to run it swings too
widely for the bound an end-to-end metric may carry (PERF.md section
2); the bounded latencies are the median and, where it repeats, the 90th percentile."""

import numpy as np

from lib import reduce


def read(obs):
    rec = obs["records"]
    if not len(rec):
        return None
    return float(np.percentile(
        reduce.latencies_ms(rec, reduce.lost_ms(obs["seconds"])), 99))
