"""Native httpd: the calls a request costs the workers over the window,
Δ(`io_calls` + `epoll_ctl_calls`) / Δ`chain_requests`: every read,
recv, send, SSL_read, SSL_write and SSL_peek on a socket and every
epoll_ctl, summed over the workers, per request answered. A proxied
keep-alive request on a pooled link makes 7 where each is made only
where it is needed. None where the program does not count its calls."""

from lib import httpd_clock, metrics


def read(obs):
    c = httpd_clock.counters(obs)
    if c is None or not c["chain_requests"]:
        return None
    io = metrics.delta(obs, {"native": "io_calls"})
    ctl = metrics.delta(obs, {"native": "epoll_ctl_calls"})
    if io is None or ctl is None:
        return None
    return (io + ctl) / c["chain_requests"]
