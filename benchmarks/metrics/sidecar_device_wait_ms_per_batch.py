"""Sidecar drain loop: milliseconds per batch the loop was BLOCKED on
the device (phase `device_wait`: the `np.asarray(dev)` sync and nothing
else), from `pingoo_sidecar_loop_ms_total`."""

from lib import xspans


def read(obs):
    return xspans.phase_ms_per_batch(obs, ("device_wait",))
