"""Native httpd, the upstream leg: the mean time in ms from a verdict's
application to the upstream's response whole (the pool or connect, the
write, the upstream's own time and the read), over the requests that
had one: Δ`chain_us{upstream}` / Δ`chain_upstream_requests`. A 403 has
no such leg. None where the program has no request chain."""

from lib import httpd_clock


def read(obs):
    c = httpd_clock.counters(obs)
    if c is None or not c["chain_upstream_requests"]:
        return None
    return c["chain_us"]["upstream"] / c["chain_upstream_requests"] / 1e3
