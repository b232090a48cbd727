"""Native httpd: the share in percent of the workers' wall time spent
outside `wait` over the window: 100 x (1 - Δ`loop_us{wait}` / Δ of all
`loop_us` phases), summed over the workers (lib/httpd_clock.py). The
phases partition each worker's time, so 100 is a worker that never
waits for an event and a few a worker that mostly spins. None where the
program has no loop account."""

from lib import httpd_clock


def read(obs):
    c = httpd_clock.counters(obs)
    if c is None:
        return None
    total = sum(c["loop_us"].values())
    if not total:
        return None
    return 100.0 * (1.0 - c["loop_us"][httpd_clock.WAIT] / total)
