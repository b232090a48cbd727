"""The plain reference of a deployment: which status must it answer?

A straightforward evaluator of the rule language over the rule SOURCES
(the same strings that go into `pingoo.yml`), written for this
benchmark and importing nothing of the program: no plan, no compiler,
no device, none of its interpreter. Semantics are the ones the
configuration states: strings are bytes (latin-1 view), `matches` is an
unanchored search with byte semantics, the first rule that matches
decides, every action is Block (403, else the upstream's 200), and a
rule whose evaluation raises is a no-match.

The grammar covered is what the configurations use: `||`, `&&`, `!`,
comparisons, `a.b` members, `lists["name"]`, and the methods
`contains`, `starts_with`, `ends_with`, `matches`, `length`. Anything
else is an error at load time, never a silent no-match.

`Reference(..., caps=...)` is the control of "How correct is decided":
the same reference with each string field cut to a staging cap before
the rules see it - the shortcut a later PR would be tempted by.
"""

from __future__ import annotations

import ipaddress
import re
from typing import Callable

import numpy as np

_TOKEN = re.compile(r"""
    \s*(?:
      (?P<str>"(?:[^"\\]|\\.)*")
    | (?P<int>\d+)
    | (?P<ident>[A-Za-z_][A-Za-z0-9_]*)
    | (?P<op>\|\||&&|==|!=|<=|>=|[<>!().\[\],])
    )""", re.VERBOSE | re.DOTALL)

_ESCAPES = {"n": "\n", "r": "\r", "t": "\t", "\\": "\\", '"': '"',
            "'": "'", "0": "\0"}


class ReferenceError_(Exception):
    """The rule source uses something this reference does not cover."""


def _unescape(body: str) -> str:
    """A string literal's bytes, as a latin-1 str. Unknown escapes stay
    as written (rule sources embed regexes such as `\\s+`)."""
    out = bytearray()
    i = 0
    while i < len(body):
        c = body[i]
        if c != "\\":
            out += c.encode("utf-8")
            i += 1
            continue
        esc = body[i + 1]
        if esc in _ESCAPES:
            out += _ESCAPES[esc].encode("utf-8")
            i += 2
        elif esc == "x":
            out.append(int(body[i + 2:i + 4], 16))
            i += 4
        elif esc == "u":
            out += chr(int(body[i + 2:i + 6], 16)).encode("utf-8")
            i += 6
        else:
            out += b"\\" + esc.encode("utf-8")
            i += 2
    return out.decode("latin-1")


def _tokens(src: str) -> list:
    out, pos = [], 0
    src = src.rstrip()
    while pos < len(src):
        m = _TOKEN.match(src, pos)
        if not m:
            raise ReferenceError_(f"cannot read {src[pos:pos + 20]!r}")
        pos = m.end()
        kind = m.lastgroup
        text = m.group(kind)
        if kind == "str":
            out.append(("str", _unescape(text[1:-1])))
        elif kind == "int":
            out.append(("int", int(text)))
        else:
            out.append((kind, text))
    out.append(("end", None))
    return out


class IpList:
    """An IP/CIDR list: exact lookups by prefix length."""

    def __init__(self, items):
        self.by_prefix: dict = {}
        for item in items:
            net = ipaddress.ip_network(str(item), strict=False)
            key = (net.version, net.prefixlen)
            self.by_prefix.setdefault(key, set()).add(
                int(net.network_address))

    def __contains__(self, ip) -> bool:
        addr = ipaddress.ip_address(ip)
        bits = 32 if addr.version == 4 else 128
        value = int(addr)
        for (version, plen), nets in self.by_prefix.items():
            if version == addr.version and \
                    (value >> (bits - plen)) << (bits - plen) in nets:
                return True
        return False


class _Parser:
    """Recursive descent to Python closures over a context dict."""

    def __init__(self, src: str):
        self.toks = _tokens(src)
        self.i = 0

    def peek(self):
        return self.toks[self.i]

    def take(self, kind=None, text=None):
        tok = self.toks[self.i]
        if (kind and tok[0] != kind) or (text and tok[1] != text):
            raise ReferenceError_(f"expected {text or kind}, got {tok}")
        self.i += 1
        return tok

    def parse(self) -> Callable:
        fn = self.or_()
        self.take("end")
        return fn

    def or_(self):
        terms = [self.and_()]
        while self.peek() == ("op", "||"):
            self.take()
            terms.append(self.and_())
        if len(terms) == 1:
            return terms[0]
        return lambda ctx: any(_truth(t(ctx)) for t in terms)

    def and_(self):
        terms = [self.not_()]
        while self.peek() == ("op", "&&"):
            self.take()
            terms.append(self.not_())
        if len(terms) == 1:
            return terms[0]
        return lambda ctx: all(_truth(t(ctx)) for t in terms)

    def not_(self):
        if self.peek() == ("op", "!"):
            self.take()
            inner = self.not_()
            return lambda ctx: not _truth(inner(ctx))
        return self.cmp()

    def cmp(self):
        left = self.postfix()
        tok = self.peek()
        if tok[0] == "op" and tok[1] in _CMP:
            self.take()
            right = self.postfix()
            op = _CMP[tok[1]]
            return lambda ctx: op(left(ctx), right(ctx))
        return left

    def postfix(self):
        node = self.primary()
        while True:
            tok = self.peek()
            if tok == ("op", "."):
                self.take()
                name = self.take("ident")[1]
                if self.peek() == ("op", "("):
                    self.take()
                    args = []
                    while self.peek() != ("op", ")"):
                        args.append(self.or_())
                        if self.peek() == ("op", ","):
                            self.take()
                    self.take("op", ")")
                    node = _method(name, node, args)
                else:
                    node = (lambda recv, key: lambda ctx: recv(ctx)[key])(
                        node, name)
            elif tok == ("op", "["):
                self.take()
                index = self.or_()
                self.take("op", "]")
                node = (lambda recv, idx: lambda ctx: recv(ctx)[idx(ctx)])(
                    node, index)
            else:
                return node

    def primary(self):
        kind, value = self.take()
        if kind in ("str", "int"):
            return lambda ctx: value
        if kind == "ident":
            if value in ("true", "false"):
                return lambda ctx: value == "true"
            return lambda ctx: ctx[value]
        if (kind, value) == ("op", "("):
            inner = self.or_()
            self.take("op", ")")
            return inner
        raise ReferenceError_(f"unexpected {value!r}")


def _truth(value) -> bool:
    if not isinstance(value, bool):
        raise TypeError("a rule's terms must be Bool")
    return value


def _same_type(fn):
    def op(a, b):
        if type(a) is not type(b):
            raise TypeError(f"cannot compare {type(a)} with {type(b)}")
        return fn(a, b)
    return op


_CMP = {"==": _same_type(lambda a, b: a == b),
        "!=": _same_type(lambda a, b: a != b),
        "<": _same_type(lambda a, b: a < b),
        "<=": _same_type(lambda a, b: a <= b),
        ">": _same_type(lambda a, b: a > b),
        ">=": _same_type(lambda a, b: a >= b)}


def _method(name: str, recv: Callable, args: list) -> Callable:
    if name == "length" and not args:
        return lambda ctx: len(recv(ctx))
    if len(args) != 1:
        raise ReferenceError_(f"{name}() takes one argument")
    arg = args[0]
    if name == "starts_with":
        return lambda ctx: _str(recv(ctx)).startswith(_str(arg(ctx)))
    if name == "ends_with":
        return lambda ctx: _str(recv(ctx)).endswith(_str(arg(ctx)))
    if name == "contains":
        def contains(ctx):
            r, a = recv(ctx), arg(ctx)
            if isinstance(r, str):
                return _str(a) in r
            return a in r   # IpList or a set of ints
        return contains
    if name == "matches":
        cache: dict = {}

        def matches(ctx):
            pattern = _str(arg(ctx))
            rx = cache.get(pattern)
            if rx is None:
                rx = cache[pattern] = re.compile(pattern.encode("latin-1"))
            return rx.search(_str(recv(ctx)).encode("latin-1")) is not None
        return matches
    raise ReferenceError_(f"method {name}() is not covered")


def _str(value) -> str:
    if not isinstance(value, str):
        raise TypeError("String expected")
    return value


def load_rules(sources: list) -> list:
    """[(name, expression)] -> [(name, predicate(ctx) -> bool)]."""
    return [(name, _Parser(src).parse()) for name, src in sources]


def load_lists(lists: dict) -> dict:
    """Lists as the reference looks things up in them: an IpList for
    addresses and networks, a set for integers."""
    out = {}
    for name, items in lists.items():
        if items and isinstance(items[0], int):
            out[name] = frozenset(items)
        else:
            out[name] = IpList(items)
    return out


#: What the server sees of the peer on loopback with no GeoIP database.
LOOPBACK_CLIENT = {"ip": "127.0.0.1", "remote_port": 40000, "asn": 0,
                   "country": "XX"}


def make_context(request: dict, lists: dict, client: dict,
                 caps: dict = None) -> dict:
    url = request["url"]
    http = {"host": request["host"], "url": url,
            "path": url.split("?", 1)[0], "method": request["method"],
            "user_agent": request["user_agent"]}
    for field, cap in (caps or {}).items():
        http[field] = http[field][:cap]
    return {"http_request": http, "client": dict(client), "lists": lists}


def _matches(pred, ctx) -> bool:
    try:
        return _truth(pred(ctx))
    except Exception:   # the configuration's guarantee: fail open
        return False


def expected_status(rules: list, ctx: dict) -> int:
    """403 when a rule matches (the first decides; all are Block),
    else the upstream's 200. A rule that raises is a no-match."""
    return 403 if any(_matches(pred, ctx) for _, pred in rules) else 200


def client_of(address: int) -> dict:
    """What the server sees of a peer at this IPv4 address."""
    return dict(LOOPBACK_CLIENT, ip=str(ipaddress.ip_address(int(address))))


class Reference:
    """expected_status for request dicts {method, host, url, user_agent}
    and the client that sends them. With `caps` it is the control of
    "How correct is decided": the same reference with each string field
    cut to `caps[field]` bytes before the rules see it (a staging cap
    below the lengths the traffic sends), the shortcut a later PR would
    be tempted by."""

    def __init__(self, sources: list, lists: dict,
                 client: dict = LOOPBACK_CLIENT, caps: dict = None):
        self.rules = load_rules(sources)
        self.lists = load_lists(lists)
        self.client = client
        self.caps = caps
        # Every action is Block, so a request is blocked when any rule
        # matches, and the rules fall into three groups by what they
        # read: the request alone, the client alone, or both.
        reads = [{text for kind, text in _tokens(src) if kind == "ident"}
                 for _, src in sources]
        self._of_request = [r for r, ids in zip(self.rules, reads)
                            if "client" not in ids]
        self._of_client = [r for r, ids in zip(self.rules, reads)
                           if "client" in ids and "http_request" not in ids]
        self._of_both = [r for r, ids in zip(self.rules, reads)
                         if "client" in ids and "http_request" in ids]
        self._by_request = (None, None)   # of the pool seen last

    def status(self, request: dict, client: dict = None) -> int:
        return expected_status(self.rules, make_context(
            request, self.lists, client or self.client, self.caps))

    def statuses(self, templates: list, tmpl: np.ndarray,
                 address: np.ndarray) -> np.ndarray:
        """The status of each of many (template, client address) pairs,
        the same as `status` gives one by one: the rules that read the
        request alone are evaluated once per template, those that read
        the client alone once per address, those that read both once
        per distinct pair."""
        if self._by_request[0] is not templates:
            self._by_request = (templates, np.array(
                [expected_status(self._of_request, make_context(
                    t, self.lists, self.client, self.caps)) == 403
                 for t in templates], bool))
        blocked = self._by_request[1][tmpl]
        if self._of_client or self._of_both:
            addrs, addr_of = np.unique(address, return_inverse=True)
            clients = [self.client if a == 0 else client_of(a)
                       for a in addrs]
            by_client = np.array(
                [expected_status(self._of_client, make_context(
                    templates[0], self.lists, c, self.caps)) == 403
                 for c in clients], bool)
            blocked = blocked | by_client[addr_of]
            if self._of_both:
                open_ = np.flatnonzero(~blocked)
                pairs, pair_of = np.unique(
                    np.stack([tmpl[open_], addr_of[open_]]), axis=1,
                    return_inverse=True)
                by_pair = np.array(
                    [expected_status(self._of_both, make_context(
                        templates[t], self.lists, clients[a],
                        self.caps)) == 403
                     for t, a in pairs.T], bool)
                blocked[open_] = by_pair[pair_of.reshape(-1)]
        return np.where(blocked, 403, 200).astype(np.uint16)
