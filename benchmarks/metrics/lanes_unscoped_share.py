"""Jitted programs: what the names miss: the device self time of
operations under no scope of the vocabulary, in percent of the device's
busy time in the traced part of the window. None where the trace holds
no scope at all (a program from before the scopes)."""

from lib import xspans


def read(obs):
    out = xspans.spans(obs)
    if not out or not out["busy_s"] or \
            set(out["by_scope"]) <= {xspans.UNSCOPED}:
        return None
    return 100.0 * out["by_scope"].get(xspans.UNSCOPED, 0.0) / out["busy_s"]
