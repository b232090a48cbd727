"""Kernels: device SELF time of Stage A per batch: the operations
traced under a `pf/<field>` scope (the literal prefilter scan of one
field and its banks' candidate counts), over the `lanes` programs that
ran in the traced part of the window. None where nothing is traced
under such a scope."""

from lib import xspans


def read(obs):
    return xspans.scoped_ms_per_batch(obs, ("pf",))
