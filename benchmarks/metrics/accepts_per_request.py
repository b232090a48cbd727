"""Native httpd: connections accepted per request parsed over the
window: Δ native `accepted` / Δ native `requests` (the listener's,
summed over its workers). A keep-alive pool at rest reads 0; every 403
and every HEAD ends its connection, so under a campaign the accept
path (the listen backlog, the accept loop, a Conn built and torn down)
is paid about once in four requests. None where the program has no
such counter (a `.json` ratio would read 0 there)."""

from lib import metrics


def read(obs):
    if "accepted" not in ((obs.get("after") or {}).get("native") or {}):
        return None
    accepted = metrics.delta(obs, {"native": "accepted"})
    requests = metrics.delta(obs, {"native": "requests"})
    if accepted is None or not requests:
        return None
    return accepted / requests
