"""Production native-plane runner: the C++ front door as THE data plane.

Topology (reference pingoo runs one Rust process, main.rs:33-85; here
the data plane is a C++ epoll process per listener and this Python
process is the policy/control plane):

    client
      -> native/httpd            public bind, TLS + SNI + acme-tls/1,
                                 h1/h2, captcha cookie gate, per-request
                                 WAF verdict enforcement, native service
                                 routing over the services table,
                                 graceful SIGTERM drain (20 s cap)
           -> upstreams          direct, chosen by the on-device route
                                 verdict (http_listener.rs:266-270 +
                                 http_proxy_service.rs:101-118 semantics)
           -> python plane       fail-open target (ring full / verdict
              (loopback)         deadline), captcha endpoints, and any
                                 service the native plane cannot carry

This process runs:
  * the full Python host plane (host/server.py) REBASED to loopback
    ports — captcha `/__pingoo/captcha*`, static sites, and the
    fail-open path all land on a complete rules-enforcing server, so
    degradation never bypasses policy;
  * the ring sidecar (device verdicts, host-rule merge, geoip
    enrichment of the C++ plane's asn/country-unknown slots);
  * a discovery republisher: every 2 s (service_registry.rs:86) the
    registry snapshot is written to the services table file, which the
    C++ plane hot-reloads on mtime change;
  * child lifecycle: SIGTERM to each httpd starts its graceful drain.

Each HTTP listener gets its OWN routing table + route lane (the
reference binds a service list per listener, config.rs:241-253);
TCP(+TLS) listeners are fronted by the same binary in --tcp-proxy mode.
"""

from __future__ import annotations

import asyncio
import os
import signal
import socket
import subprocess
from typing import Optional

from ..config.schema import Config
from ..logging_utils import get_logger
from .server import Server

log = get_logger("pingoo_tpu.native_plane")

REPUBLISH_INTERVAL_S = 2.0  # reference discovery tick, service_registry.rs:86
DRAIN_CAP_S = 20.0  # reference graceful-shutdown cap, listeners/mod.rs:28


def _loopback_rebase(config: Config) -> Config:
    """Copy the config with every HTTP listener moved to a loopback
    EPHEMERAL port (port 0 — the kernel assigns at bind time, so there
    is no pick-then-rebind race; the real ports are read back from the
    bound listeners after Server.start()). The native plane takes over
    the PUBLIC addresses."""
    import dataclasses

    from ..config.schema import ListenerProtocol

    listeners = []
    for listener in config.listeners:
        if not listener.protocol.is_http:
            # TCP(+TLS) listeners are fronted by the C++ plane in
            # tcp-proxy mode (round 5) — drop them from the Python
            # plane entirely (a loopback tcp stand-in would be a second
            # bind for no traffic; there is no fail-open for tcp).
            continue
        proto = listener.protocol
        # The Python plane sits behind the native proxy on loopback; TLS
        # terminates at the native edge, so the inner hop is plaintext.
        if proto == ListenerProtocol.HTTPS:
            proto = ListenerProtocol.HTTP
        listeners.append(dataclasses.replace(
            listener, host="127.0.0.1", port=0, protocol=proto))
    return dataclasses.replace(config, listeners=type(config.listeners)(
        listeners))


class NativePlane:
    """Owns the C++ httpd processes + ring sidecar + loopback plane."""

    def __init__(self, config: Config, state_dir: str,
                 use_device: bool = True, workers: int = 1,
                 replicas: int = 1, httpd_bin: Optional[str] = None,
                 upstream_ca: Optional[str] = None, **server_kwargs):
        from .. import native_ring

        self.config = config
        self.state_dir = state_dir
        self.workers = max(1, workers)
        self.replicas = replicas  # chips the sidecar launches batches on
        # Trust anchor for TLS upstream hops: system roots by default,
        # an explicit bundle for private-CA deployments (and tests).
        self.upstream_ca = upstream_ca or os.environ.get(
            "PINGOO_UPSTREAM_CA") or None
        self.httpd_bin = httpd_bin or os.path.join(
            native_ring.NATIVE_DIR, "httpd")
        # Per-boot token binding x-forwarded-for trust to THIS data
        # plane: the C++ workers send it on loopback control-plane hops
        # and the Python listeners trust XFF only when it matches.
        import secrets

        self._internal_token = secrets.token_hex(16)
        self._token_path = os.path.join(state_dir, "internal.token")
        tls_alpn = bool(config.tls.acme is not None
                        and config.tls.acme.domains)
        self.server = Server(_loopback_rebase(config),
                             use_device=use_device,
                             xff_token=self._internal_token,
                             tls_alpn=tls_alpn, **server_kwargs)
        self._loopback_ports: dict[str, int] = {}
        self.sidecar = None
        self._sidecar_thread = None
        self.rings = []
        self.procs: list[subprocess.Popen] = []
        self._worker_stats_fds: list[int] = []  # one memfd a listener
        self._republish_task = None
        # Per HTTP listener: its ordered http-service names and its own
        # routing-table file (the reference binds a service list PER
        # listener, config.rs:241-253 — each listener's verdict route
        # field indexes ITS table, so listeners may front different
        # service sets).
        self._listener_services: dict[str, list[str]] = {}
        self.services_paths: dict[str, str] = {}

    async def start(self) -> None:
        import threading

        from .. import native_ring
        from ..native_ring import Ring, RingSidecar

        if not await asyncio.to_thread(native_ring.ensure_built):
            raise RuntimeError(
                "native data plane requested but the C++ build failed "
                "(make -C pingoo_tpu/native)")
        os.makedirs(self.state_dir, exist_ok=True)
        # 0600 + file (not argv): /proc/<pid>/cmdline is world-readable.
        fd = os.open(self._token_path,
                     os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "w") as f:
            f.write(self._internal_token)

        await self.server.start()
        # The rebased listeners bound port 0; read the kernel-assigned
        # ports back (no pick-then-rebind TOCTOU).
        self._loopback_ports = {l.name: l.bound_port
                                for l in self.server.http_listeners}

        if any(l.protocol.is_tls for l in self.config.listeners):
            # The rebased config has no TLS listener, so Server skipped
            # TlsManager — but the NATIVE edge terminates TLS and needs
            # the store populated (first boot: the self-signed `*`
            # default, tlsmgr.py; reference tls_manager.rs:193-231).
            from .tlsmgr import TlsManager

            TlsManager(self.server.tls_dir)

        http_listeners = [l for l in self.config.listeners
                          if l.protocol.is_http]
        if not http_listeners:
            raise RuntimeError("native plane needs at least one http(s) "
                               "listener")
        for listener in http_listeners:
            self._listener_services[listener.name] = [
                n for n in listener.services if self._is_http_service(n)]
            self.services_paths[listener.name] = os.path.join(
                self.state_dir, f"services_{listener.name}.tbl")

        # One ring PER (listener, worker): the verdict queue is MPMC, so
        # two httpd processes sharing a ring would steal each other's
        # tickets (each discards tickets it does not own, and the victim
        # requests fail open at the verdict deadline).
        ring_paths: dict[tuple[str, int], str] = {}
        ring_services: list = []  # aligned with self.rings
        for listener in http_listeners:
            for w in range(self.workers):
                path = os.path.join(self.state_dir,
                                    f"ring_{listener.name}_{w}")
                ring_paths[(listener.name, w)] = path
                self.rings.append(Ring(path, capacity=16384, create=True))
                ring_services.append(
                    self._listener_services[listener.name] or None)
        self.sidecar = RingSidecar(
            self.rings, self.server.plan, self.server.lists,
            max_batch=1024, ring_services=ring_services,
            geoip=self.server.geoip, replicas=self.replicas)
        # Every chip compiles and runs its program pair before a worker
        # takes a request (a no-op on one chip).
        await asyncio.to_thread(self.sidecar.warm_replicas)
        self._sidecar_thread = threading.Thread(
            target=self.sidecar.run, daemon=True)
        self._sidecar_thread.start()

        await asyncio.to_thread(self._write_services)

        tls_dir = self.server.tls_dir
        alpn_dir = os.path.join(tls_dir, "alpn")
        for listener in http_listeners:
            fail_open_port = self._loopback_ports[listener.name]
            # One counter surface a listener: every worker writes its
            # own slot of this block and whichever of them the kernel
            # hands a /__pingoo/metrics scrape answers with all of them
            # added up (native/httpd.cc WorkerSlot). Anonymous shared
            # memory the workers inherit, not a file in the state
            # directory: the kernel writes a file mapping's dirty pages
            # back, and a counter store into a page under writeback can
            # hold a worker's event loop for seconds.
            stats_fd = os.memfd_create(f"pingoo-workers-{listener.name}")
            self._worker_stats_fds.append(stats_fd)
            for w in range(self.workers):
                argv = [
                    self.httpd_bin, str(listener.port),
                    ring_paths[(listener.name, w)],
                    "127.0.0.1", str(fail_open_port),
                    "--captcha-upstream", f"127.0.0.1:{fail_open_port}",
                    "--jwks", self.server.captcha_jwks_path,
                    "--services", self.services_paths[listener.name],
                    "--bind", listener.host,
                    "--internal-token-file", self._token_path,
                    "--worker-stats-fd", str(stats_fd),
                    "--workers", str(self.workers), "--worker", str(w),
                ]
                if listener.protocol.is_tls:
                    argv += ["--tls-dir", tls_dir]
                    if os.path.isdir(alpn_dir):
                        argv += ["--alpn-dir", alpn_dir]
                if self.upstream_ca:
                    argv += ["--upstream-ca", self.upstream_ca]
                proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                        pass_fds=(stats_fd,))
                self.procs.append(proc)  # before the bind check: a
                # failed worker must still be reaped by stop()
                try:
                    # The bind banner arrives only after cert/ring setup;
                    # a wedged child must not freeze the event loop (and
                    # with it the loopback plane + signal handling).
                    line = await asyncio.wait_for(
                        asyncio.to_thread(proc.stdout.readline), timeout=60)
                except asyncio.TimeoutError:
                    raise RuntimeError(
                        f"native httpd stalled before binding "
                        f"{listener.host}:{listener.port}")
                if b"listening" not in line:
                    raise RuntimeError(
                        f"native httpd failed to bind "
                        f"{listener.host}:{listener.port}: {line!r}")
                # Keep draining the pipe for the child's lifetime: a
                # chatty worker against a full, never-read pipe would
                # block inside the data plane.
                threading.Thread(target=self._pump_child_output,
                                 args=(proc,), daemon=True).start()
            log.info("native listener up", extra={"fields": {
                "listener": listener.name,
                "address": f"{listener.host}:{listener.port}",
                "tls": listener.protocol.is_tls,
                "workers": self.workers,
                "replicas": self.replicas,
                "rings": [os.path.basename(ring_paths[(listener.name, w)])
                          for w in range(self.workers)],
                "fail_open": f"127.0.0.1:{fail_open_port}",
            }})

        # TCP(+TLS) listeners: same binary in --tcp-proxy mode — accept
        # (+TLS terminate), pick a random upstream from the table
        # (3 tries / 3 s, tcp_proxy_service.rs:30-84), splice bytes.
        tcp_listeners = [l for l in self.config.listeners
                         if not l.protocol.is_http]
        for listener in tcp_listeners:
            # exactly one service per tcp listener (config validation)
            self._listener_services[listener.name] = list(listener.services)
            self.services_paths[listener.name] = os.path.join(
                self.state_dir, f"services_{listener.name}.tbl")
        if tcp_listeners:
            await asyncio.to_thread(self._write_services)
        for listener in tcp_listeners:
            ring_path = os.path.join(self.state_dir,
                                     f"ring_{listener.name}_tcp")
            # The ring argv is mandatory but unused in tcp mode (no
            # verdicts on raw streams — the reference evaluates rules
            # only on HTTP listeners).
            self.rings.append(Ring(ring_path, capacity=64, create=True))
            for w in range(self.workers):
                argv = [
                    self.httpd_bin, str(listener.port), ring_path,
                    "127.0.0.1", "9",  # unused: table routes instead
                    "--services", self.services_paths[listener.name],
                    "--bind", listener.host,
                    "--tcp-proxy",
                ]
                if listener.protocol.is_tls:
                    argv += ["--tls-dir", tls_dir]
                    if os.path.isdir(alpn_dir):
                        argv += ["--alpn-dir", alpn_dir]
                proc = subprocess.Popen(argv, stdout=subprocess.PIPE)
                self.procs.append(proc)
                try:
                    line = await asyncio.wait_for(
                        asyncio.to_thread(proc.stdout.readline), timeout=60)
                except asyncio.TimeoutError:
                    raise RuntimeError(
                        f"native tcp httpd stalled before binding "
                        f"{listener.host}:{listener.port}")
                if b"listening" not in line:
                    raise RuntimeError(
                        f"native tcp httpd failed to bind "
                        f"{listener.host}:{listener.port}: {line!r}")
                threading.Thread(target=self._pump_child_output,
                                 args=(proc,), daemon=True).start()
            log.info("native tcp listener up", extra={"fields": {
                "listener": listener.name,
                "address": f"{listener.host}:{listener.port}",
                "tls": listener.protocol.is_tls,
                "workers": self.workers,
            }})
        self._republish_task = asyncio.create_task(self._republish_loop())

    @staticmethod
    def _pump_child_output(proc) -> None:
        for raw in proc.stdout:
            line = raw.decode("utf-8", "replace").rstrip()
            if line:
                log.info("native httpd", extra={"fields": {
                    "pid": proc.pid, "line": line}})

    def _is_http_service(self, name: str) -> bool:
        svc = next(s for s in self.config.services if s.name == name)
        return svc.tcp_proxy is None

    def _loopback_target(self, lname: str) -> tuple:
        """The loopback control-plane hop for LISTENER lname — the
        fallback must land on the listener's OWN rebased Python
        listener (its route set), never another listener's."""
        from ..native_ring import INTERNAL

        return ("127.0.0.1", self._loopback_ports[lname], INTERNAL)

    def _service_upstreams(self, name: str) -> tuple:
        """One service's publishable (upstreams, static_root,
        needs_loopback). Plain, TLS and h2 upstreams are published
        natively; static services publish their root for in-binary
        serving of <=500KB files with the loopback Python plane as the
        streaming fallback for bigger ones; upstreams whose address
        cannot resolve are skipped (the loopback plane can still proxy
        them). The loopback entry itself is appended PER LISTENER by
        _write_services — each listener's fallback must be its own
        rebased Python listener."""
        svc = next(s for s in self.config.services if s.name == name)
        ups: list = []
        via_python = False
        static_root = None
        if svc.tcp_proxy is not None:
            # Raw TCP: no Python-plane fallback exists (and none is
            # needed — there is no verdict path to fail open from).
            # Unresolvable upstreams are simply skipped this tick; the
            # registry keeps them discovered (DNS/Docker) like any
            # other service (service_registry.rs:86).
            for u in self.server.registry.get_upstreams(name):
                addr = u.ip or u.hostname
                try:
                    addr = socket.gethostbyname(addr)
                except OSError:
                    continue
                ups.append((addr, u.port))
            return ups, None, False
        if svc.static is not None:
            root = svc.static.root
            if root and len(root) <= 383 and not any(
                    ch.isspace() for ch in root):
                static_root = root
            # the loopback plane streams >500KB files (and serves
            # everything when the root cannot be published)
            via_python = True
        else:
            from ..native_ring import H2

            for u in self.server.registry.get_upstreams(name):
                addr = u.ip or u.hostname
                try:
                    addr = socket.gethostbyname(addr)
                except OSError:
                    # Unresolvable here (or IPv6-only —
                    # gethostbyname is v4): the Python proxy can
                    # still reach it, so route via the loopback
                    # plane instead of publishing a dead service.
                    via_python = True
                    continue
                if u.h2:
                    # h2:// prior-knowledge: the C++ connector frames
                    # requests over an nghttp2 client session (round 5;
                    # TLS upstreams negotiate h2 via ALPN instead).
                    ups.append((addr, u.port, H2))
                elif u.tls:
                    # Verify against the configured name when there
                    # is one; a literal-address upstream pins the
                    # address itself (IP SAN). Unambiguous 4-tuple
                    # form: a hostname that collides with a table
                    # marker ("internal"/"h2-...") must never re-tag
                    # the hop.
                    ups.append((addr, u.port, "tls", u.hostname or addr))
                else:
                    ups.append((addr, u.port))
        return ups, static_root, via_python

    def _write_services(self) -> None:
        """Snapshot the registry into each listener's OWN routing table
        (runs in a worker thread: gethostbyname blocks). A listener's
        verdict route field indexes the order of ITS service list, so
        every table is written in that listener's order (reference:
        per-listener service binding, config.rs:241-253)."""
        from ..native_ring import write_services_file

        resolved = {name: self._service_upstreams(name)
                    for names in self._listener_services.values()
                    for name in names}
        for lname, names in self._listener_services.items():
            table = []
            for n in names:
                ups, static_root, needs_loopback = resolved[n]
                if needs_loopback and lname in self._loopback_ports:
                    ups = ups + [self._loopback_target(lname)]
                table.append((n, ups, static_root))
            write_services_file(self.services_paths[lname], table)

    async def _republish_loop(self) -> None:
        last = None
        while True:
            await asyncio.sleep(REPUBLISH_INTERVAL_S)
            try:
                snapshot = [
                    (n, tuple(
                        (u.ip or u.hostname, u.port, u.tls)
                        for u in self.server.registry.get_upstreams(n)))
                    for names in self._listener_services.values()
                    for n in names
                ]
                if snapshot != last:
                    await asyncio.to_thread(self._write_services)
                    last = snapshot
            except Exception as exc:  # keep the loop alive on blips
                log.warning("services republish failed",
                            extra={"fields": {"error": repr(exc)}})

    async def serve_forever(self) -> None:
        await self.server.serve_forever()

    async def stop(self) -> None:
        if self._republish_task is not None:
            self._republish_task.cancel()
        # Graceful drain: SIGTERM starts the C++ plane's connection
        # drain; it exits when idle or at its internal cap.
        for proc in self.procs:
            log.info("draining native worker", extra={"fields": {
                "pid": proc.pid, "poll": proc.poll()}})
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        deadline = asyncio.get_event_loop().time() + DRAIN_CAP_S
        for proc in self.procs:
            remaining = deadline - asyncio.get_event_loop().time()
            try:
                await asyncio.wait_for(
                    asyncio.to_thread(proc.wait),
                    timeout=max(0.5, remaining))
            except asyncio.TimeoutError:
                proc.kill()
        if self.sidecar is not None:
            self.sidecar.stop()
        if self._sidecar_thread is not None:
            self._sidecar_thread.join(timeout=10)
        for ring in self.rings:
            ring.close()
        for fd in self._worker_stats_fds:
            os.close(fd)
        self._worker_stats_fds.clear()
        await self.server.stop()


async def run_native(config: Config, state_dir: str, **kwargs) -> None:
    """Native-plane main(): build, serve, drain on SIGINT/SIGTERM."""
    plane = NativePlane(config, state_dir, **kwargs)
    try:
        await plane.start()
    except BaseException:
        # Partial startup must not orphan C++ workers holding public
        # ports (their ring would have no consumer once we exit).
        await plane.stop()
        raise
    loop = asyncio.get_running_loop()
    stop_event = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop_event.set)
        except NotImplementedError:
            pass
    serve_task = asyncio.create_task(plane.serve_forever())
    await stop_event.wait()
    log.info("shutdown signal: draining native plane")
    serve_task.cancel()
    await plane.stop()
    log.info("native plane drained")
