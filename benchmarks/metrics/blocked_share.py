"""Native httpd: of the requests the listener parsed over the window,
the share in percent that a verdict answered 403: Δ native `blocked` /
Δ native `requests` (the listener's, summed over its workers). What the
request mix makes of the deployment's rules and lists as the window saw
it: a quarter under a scanning campaign, a twentieth on `web`. None
where the native plane gave no counters."""

from lib import metrics


def read(obs):
    blocked = metrics.delta(obs, {"native": "blocked"})
    requests = metrics.delta(obs, {"native": "requests"})
    if blocked is None or not requests:
        return None
    return 100.0 * blocked / requests
