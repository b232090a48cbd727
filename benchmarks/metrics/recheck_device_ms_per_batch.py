"""Kernels: device SELF time under an `nfa/*` scope per batch: the
serial exact scans. Since the recheck of an approximate DFA's flagged
rows runs under `nfa/<bank>` nested in `dfa/<bank>`, that is the
recheck ladder (its argsort, gather, exact scan and scatter) together
with the banks that have no DFA at all (`nfa/user_agent`), over the
`lanes` programs that ran in the traced part of the window; the DFA's
own gather ladder stays under `dfa/*`. On a program without the nested
scope it reads the banks without a DFA alone. None where the trace
holds no `nfa/*` scope."""

from lib import xspans


def read(obs):
    return xspans.scoped_ms_per_batch(obs, ("nfa",))
