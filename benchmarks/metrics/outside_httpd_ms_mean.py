"""Served path, client side: of a request's round trip as the client
sees it, the mean in ms that no httpd stamp covers: the generator's
mean (done - sent) over the requests answered that it sent between the
window's two counter snapshots, less the mean of the request chain in
httpd over the same snapshots (Δ of all `chain_us` / Δ`chain_requests`:
first byte read to last byte written). What is left is the client, the
kernel's socket queues both ways, and a process waiting for a core.
None where the program has no request chain."""

import numpy as np

from lib import httpd_clock


def read(obs):
    c = httpd_clock.counters(obs)
    rec, t0 = obs.get("all_records"), obs.get("gen_t0_mono")
    a = (obs.get("before") or {}).get("at_mono")
    b = (obs.get("after") or {}).get("at_mono")
    if c is None or not c["chain_requests"] or rec is None or None in (
            t0, a, b):
        return None
    lo, hi = (a - t0) * 1e9, (b - t0) * 1e9
    sent = rec[(rec["outcome"] == 0) & (rec["sent_ns"] >= lo)
               & (rec["sent_ns"] < hi)]
    if not len(sent):
        return None
    round_trip = float(np.mean(sent["done_ns"] - sent["sent_ns"])) / 1e6
    return round_trip - sum(c["chain_us"].values()) / c["chain_requests"] / 1e3
