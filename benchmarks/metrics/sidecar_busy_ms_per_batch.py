"""Sidecar drain loop: milliseconds per batch the loop WORKED, from its
own phase account (`pingoo_sidecar_loop_ms_total{phase}`, a partition of
the drain thread's time): every phase but the wait for the device and
the idle stretches, over the batches served in the window. With
`sidecar_device_wait_ms_per_batch` and `sidecar_idle_ms_per_batch` it
adds up to the batch period (the window's seconds over its batches)."""

from lib import xspans


def read(obs):
    return xspans.phase_ms_per_batch(obs, xspans.BUSY_PHASES)
