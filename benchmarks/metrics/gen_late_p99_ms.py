"""How late the generator ran: 99th percentile, over the requests of
the window that were sent, of (time sent - time due), in ms. A starved
generator must not be read as a fast server; in a steady cell a late
send also means every connection was busy."""

import numpy as np


def read(obs):
    rec = obs["records"]
    sent = rec[rec["sent_ns"] >= 0]
    if not len(sent):
        return None
    return float(np.percentile((sent["sent_ns"] - sent["due_ns"]) / 1e6, 99))
