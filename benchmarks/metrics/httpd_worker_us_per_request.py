"""Native httpd: the workers' µs a request costs over the window, the
time outside `wait` summed over the workers over the requests answered:
(Δ of all `loop_us` phases − Δ`loop_us{wait}`) / Δ`chain_requests`.
On a saturated worker its inverse is the rate that worker can serve.
None where the program has no loop account."""

from lib import httpd_clock


def read(obs):
    c = httpd_clock.counters(obs)
    if c is None or not c["chain_requests"]:
        return None
    busy = sum(c["loop_us"].values()) - c["loop_us"][httpd_clock.WAIT]
    return busy / c["chain_requests"]
