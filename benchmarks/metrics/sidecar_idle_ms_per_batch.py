"""Sidecar drain loop: milliseconds per batch the loop had NOTHING TO DO
(phase `idle`: from the first empty pass with nothing in flight to the
next pass that gets rows, the sleeps with it), from
`pingoo_sidecar_loop_ms_total`. The loop's headroom: marked `higher`."""

from lib import xspans


def read(obs):
    return xspans.phase_ms_per_batch(obs, ("idle",))
