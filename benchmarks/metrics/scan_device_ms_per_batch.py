"""Kernels: device SELF time of the scans per batch: the operations
traced under an `nfa/*`, `dfa/*` or `win/*` scope (one serial NFA bank,
one bitsplit-DFA bank with its recheck, the window banks), over the
`lanes` programs that ran in the traced part of the window. None where
the configuration has no such bank (or the program no such scope)."""

from lib import xspans


def read(obs):
    return xspans.scoped_ms_per_batch(obs, xspans.SCAN_KINDS)
