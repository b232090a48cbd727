"""One general traffic generator: a request mix's parameters -> a pool
of request templates, a schedule of (due time, template) for an open
loop or a sequence of templates for a closed one, and the source
address of every connection. Everything a mix is made of is data in its
file (hosts, user-agent strings, payloads, lengths, shares, client
addresses): a new mix is a new file, never new code. How a mix is
offered (loop, rate, connections) is the traffic file's that names it.

Two seeds. The mix's own `shape_seed` (in the traffic file) fixes the
WORK: every template's method, host, path length, URL length,
user-agent, payload and popularity rank, the multiset of arrival gaps
and the multiset of ranks requested. `--seed` changes only what does
not change the work: the letters that fill the lengths, and the ORDER
of gaps and of ranks. So two seeds offer the system the same set of
sizes and arrivals in another order, and a difference between seeds is
noise, not workload.
"""

from __future__ import annotations

import ipaddress
import math

import numpy as np

_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789", np.uint8)


def _lognormal(rng, median: float, p95: float, size: int) -> np.ndarray:
    sigma = math.log(p95 / median) / 1.6449
    return np.exp(rng.normal(math.log(median), sigma, size))


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    return w / w.sum()


def _pick_weighted(rng, weights: np.ndarray, share: float,
                   taken: np.ndarray) -> np.ndarray:
    """Indices whose weights add up to about `share`, none of them so
    heavy that it would be a large part of the share by itself."""
    chosen, total = [], 0.0
    for i in rng.permutation(len(weights)):
        if taken[i] or weights[i] > share / 4 or total >= share:
            continue
        chosen.append(i)
        total += weights[i]
    return np.array(chosen, dtype=np.int64)


def _fill(rng, n: int) -> str:
    return _LETTERS[rng.integers(0, len(_LETTERS), n)].tobytes().decode()


def _clean_path(rng, length: int) -> str:
    """`/seg/seg/...` of exactly `length` bytes (>= 1)."""
    out = "/"
    while len(out) < length:
        seg = _fill(rng, int(rng.integers(2, 12)))
        out += seg + "/"
    out = out[:length]
    return out if len(out) == 1 or out[-1] != "/" else out[:-1] + "x"


def _clean_query(rng, length: int) -> str:
    """`?k=v&k=v...` of exactly `length` bytes (>= 4)."""
    out = "?"
    while len(out) < length:
        out += _fill(rng, int(rng.integers(1, 8))) + "=" + \
            _fill(rng, int(rng.integers(1, 24))) + "&"
    out = out[:length]
    return out[:-1] + "x" if out[-1] in "&=?" else out


class Mix:
    """The templates' structure, from the mix file alone."""

    def __init__(self, spec: dict):
        self.spec = spec
        rng = np.random.default_rng(spec["shape_seed"])
        n = spec["pool"]["templates"]
        self.weights = zipf_weights(n, spec["pool"]["zipf_s"])
        p = spec["path"]
        self.path_len = np.clip(np.rint(_lognormal(
            rng, p["median"], p["p95"], n)), 1, p["cap"]).astype(np.int64)
        q = spec["query"]
        has_query = rng.random(n) < q["share"]
        qlen = np.clip(np.rint(_lognormal(rng, q["median"], q["p95"], n)),
                       4, None).astype(np.int64)
        self.query_len = np.where(has_query, qlen, 0)
        self.query_len = np.minimum(self.query_len,
                                    q["url_cap"] - self.path_len)
        self.query_len[self.query_len < 4] = 0
        methods = list(spec["methods"])
        self.method = rng.choice(len(methods), n,
                                 p=list(spec["methods"].values()))
        self.method[:4] = methods.index("GET")   # the heaviest ranks
        self.methods = methods
        self.host = rng.integers(0, len(spec["hosts"]), n)
        ua = spec["user_agents"]
        shares = np.array([w for w, _ in ua["browsers"]], np.float64)
        self.ua = rng.choice(len(shares), n, p=shares / shares.sum())
        taken = np.zeros(n, bool)
        self.scanner = _pick_weighted(rng, self.weights,
                                      ua["scanner_share"], taken)
        taken[self.scanner] = True
        self.scanner_ua = rng.integers(0, len(ua["scanners"]), n)
        self.attack = _pick_weighted(rng, self.weights,
                                     spec["payloads"]["share"], taken)
        self.attack_kind = rng.integers(0, 3, n)   # query / head / tail
        self.attack_pick = rng.integers(0, 1 << 30, n)
        self.n = n

    def templates(self, seed: int) -> list:
        """The pool for one run: [{method, host, url, user_agent}]."""
        rng = np.random.default_rng([self.spec["shape_seed"], seed, 1])
        is_attack = np.zeros(self.n, bool)
        is_attack[self.attack] = True
        is_scanner = np.zeros(self.n, bool)
        is_scanner[self.scanner] = True
        cap = self.spec["query"]["url_cap"]
        pay = self.spec["payloads"]
        uas = self.spec["user_agents"]
        hosts = self.spec["hosts"]
        out = []
        for i in range(self.n):
            path = _clean_path(rng, int(self.path_len[i]))
            query = (_clean_query(rng, int(self.query_len[i]))
                     if self.query_len[i] else "")
            if is_attack[i]:
                pick, kind = int(self.attack_pick[i]), self.attack_kind[i]
                if kind == 0:     # in the query of a clean URL
                    payload = pay["query"][pick % len(pay["query"])]
                    query = (query + "&" if query else "?") + payload
                elif kind == 1:   # a probed path in front of a clean one
                    path = pay["path_head"][
                        pick % len(pay["path_head"])].rstrip("/") + path
                else:             # a clean path with a probe's tail
                    path = path.rstrip("/") + \
                        pay["path_tail"][pick % len(pay["path_tail"])]
            url = (path + query)[:cap]
            ua = (uas["scanners"][int(self.scanner_ua[i])] if is_scanner[i]
                  else uas["browsers"][int(self.ua[i])][1])
            out.append({"method": self.methods[int(self.method[i])],
                        "host": hosts[int(self.host[i])],
                        "url": url, "user_agent": ua})
        return out

    def addresses(self, slots: int, listed: list = ()) -> np.ndarray:
        """The IPv4 source address (host byte order) of each of the
        generator's `slots` connections: `clients.addresses` distinct
        ones inside `clients.net`, of which the share `listed_share`
        are taken from `listed` (the deployment's IP lists: single
        addresses and members of its networks that lie in the net),
        where it has any. From the mix's own seed alone: the same
        clients whatever `--seed`. Zeros (the kernel's choice) for a
        mix with no `clients`."""
        spec = self.spec.get("clients")
        if not spec:
            return np.zeros(slots, np.uint32)
        rng = np.random.default_rng([self.spec["shape_seed"], 3])
        net = ipaddress.ip_network(spec["net"])
        lo, size = int(net.network_address), net.num_addresses
        want = int(spec["addresses"])
        inside = []
        for item in listed:
            item_net = ipaddress.ip_network(str(item), strict=False)
            if item_net.version == 4 and item_net.subnet_of(net):
                first = int(item_net.network_address)
                # a member of a listed network, not its .0
                inside.append(first + (item_net.num_addresses > 1)
                              * int(rng.integers(1, max(
                                  2, item_net.num_addresses - 1))))
        n_listed = min(len(inside), int(round(want * spec["listed_share"])))
        chosen = [inside[i] for i in
                  rng.choice(len(inside), n_listed, replace=False)] \
            if n_listed else []
        pool = set(chosen)
        while len(pool) < want:
            pool.add(lo + int(rng.integers(2, size - 1)))
        pool = np.array(sorted(pool), np.uint32)
        rng.shuffle(pool)
        return pool[np.arange(slots) % len(pool)]

    def schedule(self, seed: int, rate_rps: float, seconds: float,
                 salt: int = 0) -> tuple:
        """Open loop, Poisson arrivals -> (due_ns int64[n], template
        uint32[n]): n = rate*seconds requests whose gaps and ranks are
        the mix's own multisets for this (rate, seconds), in an order
        drawn from `seed`."""
        n = max(1, int(round(rate_rps * seconds)))
        shape = np.random.default_rng(
            [self.spec["shape_seed"], n, int(seconds * 1000)])
        gaps = shape.exponential(1.0, n)
        ranks = shape.choice(self.n, n, p=self.weights).astype(np.uint32)
        order = np.random.default_rng([self.spec["shape_seed"], seed,
                                       2, salt])
        # n requests have n - 1 gaps between them: the last one drawn
        # closes the window and is never permuted in, so every seed
        # uses the same n - 1.
        used = order.permutation(gaps[:-1])
        ranks = order.permutation(ranks)
        due = np.concatenate(([0.0], np.cumsum(used))) / gaps.sum() * seconds
        return (due * 1e9).astype(np.int64), ranks

    def sequence(self, seed: int, n: int, salt: int = 0) -> np.ndarray:
        """Closed loop -> template uint32[n]: the mix's own multiset of
        n ranks, in an order drawn from `seed`. The connections send
        them in this order, each its next one when its reply is in."""
        shape = np.random.default_rng([self.spec["shape_seed"], n, 4])
        ranks = shape.choice(self.n, n, p=self.weights).astype(np.uint32)
        return np.random.default_rng(
            [self.spec["shape_seed"], seed, 5, salt]).permutation(ranks)


def wire_request(t: dict) -> bytes:
    """HTTP/1.1 bytes of a template; the target goes out raw (the
    server splits the request line on its first and last space)."""
    head = (f"{t['method']} {t['url']} HTTP/1.1\r\nhost: {t['host']}\r\n"
            f"user-agent: {t['user_agent']}\r\n")
    if t["method"] == "POST":
        head += "content-length: 0\r\n"
    return (head + "\r\n").encode("latin-1")
