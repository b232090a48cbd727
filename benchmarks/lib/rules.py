"""Rule sources of the deployments, made by the benchmark itself.

`crs_rule_sources` is a copy of the program's
`pingoo_tpu/utils/crs.generate_rule_sources` as of PR 21 (same pools,
same draws from the same seed, so the same 500 expressions and the same
two lists), kept here so that a later PR cannot change the deployment
the benchmark measures. Lists hold plain strings and ints, not the
program's `Ip` values. A configuration file names the generator and its
inputs under `rules`, or gives literal expressions.
"""

from __future__ import annotations

import random

SQLI_CORES = [
    r"(?i)\bunion\s+select\b", r"(?i)select\s+.{0,10}from", r"(?i)insert\s+into",
    r"(?i)delete\s+from", r"(?i)drop\s+table", r"(?i)\bor\b\s+1=1",
    r"(?i)\band\b\s+1=1", r"(?i)sleep\(\d+\)", r"(?i)benchmark\(",
    r"(?i)waitfor\s+delay", r"(?i)group\s+by.{0,8}having", r"(?i)into\s+outfile",
    r"(?i)load_file\(", r"(?i)information_schema", r"'\s*--", r"(?i)xp_cmdshell",
    r"(?i)\bexec\b", r"(?i)\bcast\(", r"(?i)\bconcat\(",
]
XSS_CORES = [
    r"(?i)<script", r"(?i)javascript:", r"(?i)onerror\s*=", r"(?i)onload\s*=",
    r"(?i)<iframe", r"(?i)document\.cookie", r"(?i)alert\(", r"%3[Cc]script",
    r"(?i)<svg[^>]{0,20}onload", r"(?i)eval\(", r"(?i)expression\(",
    r"(?i)vbscript:", r"(?i)src\s*=\s*data:",
    # Real CRS signatures routinely exceed 31 NFA positions (multi-word
    # packing, compiler/nfa.py pack_span):
    r"(?i)<svg[^>]{0,40}on(load|error)\s{0,8}=",
    r"(?i)<(img|input|body)[^>]{0,40}on[a-z]{4,12}\s{0,4}=",
    r"(?i)String\.fromCharCode\([0-9, ]{0,40}\)",
]
LFI_RCE_CORES = [
    r"\.\./", r"\.\.%2[fF]", r"/etc/passwd", r"/etc/shadow", r"(?i)c:\\windows",
    r"(?i)cmd\.exe", r"(?i)/bin/(ba)?sh", r"%00", r"(?i)php://input",
    r"(?i)file://", r"(?i)expect://", r"(?i)proc/self/environ",
    r"(?i)wget\s+http", r"(?i)curl\s+http", r";\s*cat\s", r"\|\s*id\s*$",
    r"(?i)(\.\./){3,12}etc/(passwd|shadow|group)",  # deep traversal chains
    r"(?i)union[\s/\*]{1,20}(all[\s/\*]{1,20})?select",  # comment-evasion SQLi
]
SCANNER_UAS = [
    r"(?i)sqlmap", r"(?i)nikto", r"(?i)nessus", r"(?i)masscan", r"(?i)nmap",
    r"(?i)dirbuster", r"(?i)gobuster", r"(?i)wpscan", r"(?i)acunetix",
    r"(?i)zgrab", r"(?i)python-requests/1\.", r"(?i)go-http-client",
]
BAD_PREFIXES = [
    "/.env", "/.git", "/.svn", "/.hg", "/.aws", "/wp-admin", "/wp-login",
    "/phpmyadmin", "/pma", "/admin/config", "/cgi-bin", "/.well-known/../",
    "/vendor/phpunit", "/solr/admin", "/jenkins", "/manager/html",
    "/actuator", "/.DS_Store", "/server-status", "/debug/pprof",
]
BAD_SUFFIXES = [
    ".php.bak", ".sql", ".sqlite", ".pem", ".key", ".p12", ".bak", ".old",
    ".swp", "~", ".config", ".ini", ".log", ".tar.gz", ".zip.enc",
]
BAD_EXACT = [
    "/config.json", "/backup.zip", "/dump.sql", "/id_rsa", "/.htpasswd",
    "/web.config", "/composer.lock", "/package-lock.json.orig",
]


def crs_rule_sources(
    num_rules: int = 500,
    seed: int = 20260728,
    with_lists: bool = True,
    list_sizes: tuple[int, int] = (4096, 512),
) -> tuple:
    """(rule name, expression) pairs and the lists they name; every
    rule's action is Block."""
    rng = random.Random(seed)
    sources: list[tuple[str, str]] = []  # (name, expression)

    def add(name, src):
        sources.append((f"{name}_{len(sources):04d}", src))

    fields = ["http_request.url", "http_request.path"]
    regex_cores = (
        [("sqli", c) for c in SQLI_CORES]
        + [("xss", c) for c in XSS_CORES]
        + [("lfi", c) for c in LFI_RCE_CORES]
    )
    # Expand cores with suffix/prefix variations to reach scale, CRS-style
    # (many rules per attack class, each a distinct signature).
    variations = ["", r"\s*\(", r"\s*=", r"[%+]", r"\d", r"['\"]", r"/",
                  r"\s+[a-z]+", r"[a-z]{0,4}\("]
    target_regex = int(num_rules * 0.55)
    i = 0
    while sum(1 for n, _ in sources if not n.startswith("ua_")) < target_regex:
        klass, core = regex_cores[i % len(regex_cores)]
        var = variations[(i // len(regex_cores)) % len(variations)]
        field = fields[i % 2]
        pattern = core + var if (i // len(regex_cores)) else core
        i += 1
        add(klass, f'{field}.matches("{_escape(pattern)}")')

    for ua in SCANNER_UAS:
        add("ua", f'http_request.user_agent.matches("{_escape(ua)}")')

    for p in BAD_PREFIXES:
        add("prefix", f'http_request.path.starts_with("{p}")')
    for s in BAD_SUFFIXES:
        add("suffix", f'http_request.path.ends_with("{s}")')
    for e in BAD_EXACT:
        add("exact", f'http_request.path == "{e}"')

    # contains() keyword rules
    for kw in ["passwd", "boot.ini", "win.ini", "/../..", "base64,",
               "<?php", "${jndi:", "{{7*7}}", "__proto__", "ognl."]:
        add("kw", f'http_request.url.contains("{kw}")')

    # numeric / metadata rules (geo + asn + shape, BASELINE config 4)
    add("geo", 'client.country == "KP"')
    add("geo", '(client.country == "RU" || client.country == "IR") && '
               'http_request.path.starts_with("/admin")')
    add("shape", "http_request.path.length() > 200")
    add("shape", "http_request.user_agent.length() == 0")
    add("shape", "client.remote_port < 1024 && client.remote_port != 80 && "
                 "client.remote_port != 443")

    lists: dict[str, list] = {}
    if with_lists:
        n_ips, n_asns = list_sizes
        lists["blocked_ips"] = _random_ip_list(rng, n_ips)
        lists["blocked_asns"] = sorted(rng.sample(range(1000, 400000), n_asns))
        add("list", 'lists["blocked_ips"].contains(client.ip)')
        add("list", 'lists["blocked_asns"].contains(client.asn)')

    # Top up to num_rules with generated literal-keyword rules.
    sig = 0
    while len(sources) < num_rules:
        token = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz_")
                        for _ in range(rng.randint(5, 10)))
        which = sig % 3
        if which == 0:
            add("gen", f'http_request.url.contains("{token}")')
        elif which == 1:
            add("gen", f'http_request.path.starts_with("/{token}")')
        else:
            add("gen", f'http_request.url.matches("(?i){token}[0-9a-f]*")')
        sig += 1
    return sources[:num_rules], lists


def _escape(pattern: str) -> str:
    return pattern.replace("\\", "\\\\").replace('"', '\\"')


def _random_ip_list(rng: random.Random, n: int) -> list:
    out = []
    for _ in range(n - n // 16):
        out.append((f"{rng.randrange(1, 224)}.{rng.randrange(256)}."
                      f"{rng.randrange(256)}.{rng.randrange(256)}"))
    for _ in range(n // 16):
        out.append((f"{rng.randrange(1, 224)}.{rng.randrange(256)}."
                      f"{rng.randrange(256)}.0/24"))
    return out


def rule_sources(spec: dict) -> tuple:
    """-> ([(name, expression)], {list name: items}) for a
    configuration's `rules` entry."""
    if "literal" in spec:
        return [tuple(pair) for pair in spec["literal"]], {}
    if spec.get("generator") == "crs":
        return crs_rule_sources(spec["num_rules"], spec["rule_seed"], True,
                                tuple(spec["list_sizes"]))
    raise ValueError(f"unknown rule source {sorted(spec)}")
