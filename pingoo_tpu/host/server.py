"""Server orchestrator: wire config -> registry, geoip, captcha, lists,
verdict engine, services, TLS, listeners; run until shutdown.

Reference parity (pingoo/server.rs:33-150 + main.rs:33-107): build the
service registry and start background discovery, load geoip (optional),
captcha manager, lists; construct per-listener service sets; TLS manager
for https/tcp+tls listeners; bind everything, then serve concurrently
with graceful shutdown. The addition over the reference is the
VerdictService between listeners and rules: the ruleset is compiled once
at boot (config errors fail fast, as in the reference where expressions
compile during config load) into the TPU plan + device tables.
"""

from __future__ import annotations

import asyncio
import os
import signal
from typing import Optional

from ..compiler import compile_ruleset
from ..config.schema import Config, ListenerProtocol
from ..engine.service import VerdictService
from ..lists import load_lists
from .captcha import CaptchaManager
from .discovery import ServiceRegistry
from .geoip import GeoipDB
from .httpd import HttpListener
from .services import TcpProxyService, build_http_services
from .tlsmgr import TlsManager


class Server:
    def __init__(
        self,
        config: Config,
        use_device: bool = True,
        geoip_paths: Optional[tuple] = None,
        captcha_jwks_path: str = "/etc/pingoo/captcha_jwks.json",
        tls_dir: str = "/etc/pingoo/tls",
        enable_docker: bool = True,
        cache_dir: Optional[str] = None,
        bot_score_params_path: Optional[str] = None,
        xff_token: Optional[str] = None,
        tls_alpn: bool = False,
    ):
        self.config = config
        self.use_device = use_device
        self.geoip_paths = geoip_paths
        self.captcha_jwks_path = captcha_jwks_path
        self.tls_dir = tls_dir
        self.enable_docker = enable_docker
        self.cache_dir = cache_dir
        self.bot_score_params_path = bot_score_params_path
        # Deployment flags the native-plane runner passes EXPLICITLY
        # (they used to travel via process-global env vars, which let
        # any co-resident Server instance inherit them):
        # - xff_token: per-boot token; the listeners trust
        #   x-forwarded-for ONLY on requests carrying it (the C++ data
        #   plane sends it on loopback control-plane hops).
        # - tls_alpn: the native TLS transport fronts the public ports,
        #   so ACME validates via tls-alpn-01 (http-01 would hit the
        #   native verdict path, not the challenge handler).
        self.xff_token = xff_token
        self.tls_alpn = tls_alpn
        self.registry: Optional[ServiceRegistry] = None
        self.verdict: Optional[VerdictService] = None
        self.http_listeners: list[HttpListener] = []
        self.tcp_servers: list[asyncio.AbstractServer] = []
        self.acme = None

    async def start(self) -> None:
        config = self.config
        self.registry = ServiceRegistry(
            config.services,
            docker_socket=config.service_discovery.docker_socket,
            enable_docker=self.enable_docker)
        await self.registry.start_in_background()

        geoip = (GeoipDB.load(self.geoip_paths) if self.geoip_paths
                 else GeoipDB.load())
        captcha = CaptchaManager(self.captcha_jwks_path)
        lists = load_lists(config.lists)
        # Exposed for the native-plane runner (host/native_plane.py):
        # its ring sidecar shares this plan/lists/geoip so the C++ front
        # door and the Python plane compute identical verdicts.
        self.geoip = geoip
        self.lists = lists

        from ..compiler.cache import compile_ruleset_cached

        # Serving-mesh + scheduler knobs (ISSUE 6, docs/SCHEDULER.md):
        # validate PINGOO_MESH here so a malformed spec fails the boot
        # with its message instead of silently serving unsharded, and
        # log the admission policy the engine planes will run under.
        from ..sched import SchedulerConfig, mesh_env_spec

        mesh_spec = mesh_env_spec()  # raises ValueError on a bad spec
        sched_cfg = SchedulerConfig.from_env(max_batch=1024)
        from ..logging_utils import get_logger

        get_logger("pingoo_tpu.server").info(
            "scheduler config", extra={"fields": {
                "mesh": "x".join(str(d) for d in mesh_spec),
                "mode": sched_cfg.mode,
                "deadline_ms": sched_cfg.deadline_ms,
                "failopen": sched_cfg.failopen,
            }})

        # Service route predicates compile into the same plan as extra
        # verdict columns (rules AND routing decided by one batch).
        routes = [(s.name, s.route) for s in config.services]
        plan = compile_ruleset_cached(
            list(config.rules), lists, cache_dir=self.cache_dir,
            routes=routes)
        self.plan = plan
        bot_params = None
        if self.bot_score_params_path:
            from ..models.botscore import load_params

            bot_params = load_params(self.bot_score_params_path)
        # The backend is whatever jax initialises in THIS process — no
        # probe, no fallback; --no-device pinned the CPU in __main__
        # before first use (pingoo_tpu/backend.py).
        self.verdict = VerdictService(plan, lists,
                                      use_device=self.use_device,
                                      bot_score_params=bot_params)
        await self.verdict.start()
        # Boot-time degradation surface (ISSUE 10, docs/RESILIENCE.md):
        # rungs already demoted at startup (broken backend, mesh spec
        # too big) are easy to miss in counters — log them once, here.
        demoted = self.verdict.ladder.demoted()
        if demoted:
            get_logger("pingoo_tpu.server").warning(
                "boot with demoted rungs", extra={"fields": {
                    "demoted": demoted,
                    "ladder": self.verdict.ladder.snapshot()}})

        tls_manager: Optional[TlsManager] = None
        if any(l.protocol.is_tls for l in config.listeners) or \
                config.tls.acme is not None:
            tls_manager = TlsManager(self.tls_dir)

        acme_challenges = None
        if config.tls.acme is not None and config.tls.acme.domains:
            from .acme import AcmeManager

            # Challenge type is an EXPLICIT deployment choice:
            # tls_alpn=True means the native TLS transport fronts
            # port 443 and answers acme-tls/1 from <tls_dir>/alpn
            # (tls-alpn-01, the reference's only challenge type,
            # acme.rs:180-242). Without it, the Python-only deployment
            # uses http-01 — inferring the mode from directory existence
            # would silently break issuance either way.
            alpn_dir = None
            if self.tls_alpn:
                alpn_dir = os.path.join(self.tls_dir, "alpn")
                os.makedirs(alpn_dir, exist_ok=True)
            self.acme = AcmeManager(
                self.tls_dir, list(config.tls.acme.domains),
                directory_url=config.tls.acme.directory_url,
                tls_manager=tls_manager, alpn_dir=alpn_dir)
            acme_challenges = self.acme.challenges
            await self.acme.start_in_background()


        services_by_name = {s.name: s for s in config.services}
        for listener_cfg in config.listeners:
            listener_services = [services_by_name[n]
                                 for n in listener_cfg.services]
            if listener_cfg.protocol.is_http:
                http_services = build_http_services(
                    listener_services, self.registry)
                listener = HttpListener(
                    name=listener_cfg.name,
                    host=listener_cfg.host,
                    port=listener_cfg.port,
                    services=http_services,
                    verdict=self.verdict,
                    lists=lists,
                    rules_meta=plan.rules,
                    captcha=captcha,
                    geoip=geoip,
                    tls_context=(tls_manager.server_context()
                                 if listener_cfg.protocol.is_tls else None),
                    acme_challenges=acme_challenges,
                    xff_token=self.xff_token,
                    # Columns are looked up by the BUILT services' names:
                    # build_http_services may drop non-http entries, so a
                    # positional zip against the config list could hand a
                    # service another service's route column.
                    route_indices=[plan.route_index.get(s.name)
                                   for s in http_services],
                )
                await listener.bind()
                self.http_listeners.append(listener)
            else:
                svc = TcpProxyService(listener_services[0], self.registry)
                ssl_ctx = (tls_manager.server_context()
                           if listener_cfg.protocol.is_tls else None)
                server = await asyncio.start_server(
                    svc.serve_connection, listener_cfg.host,
                    listener_cfg.port, ssl=ssl_ctx, backlog=2048)
                self.tcp_servers.append(server)

    async def serve_forever(self) -> None:
        tasks = [asyncio.create_task(l.serve_forever())
                 for l in self.http_listeners]
        tasks += [asyncio.create_task(s.serve_forever())
                  for s in self.tcp_servers]
        if tasks:
            await asyncio.gather(*tasks)

    async def stop(self) -> None:
        for listener in self.http_listeners:
            await listener.close()
            for service in listener.services:
                close = getattr(service, "close", None)
                if close is not None:
                    await close()
        for server in self.tcp_servers:
            server.close()
            await server.wait_closed()
        if self.acme is not None:
            await self.acme.stop()
        if self.verdict is not None:
            await self.verdict.stop()
        if self.registry is not None:
            await self.registry.stop()


async def run(config: Config, **kwargs) -> None:
    """main() equivalent (reference main.rs:33-85): build, serve, and
    shut down gracefully on SIGINT/SIGTERM."""
    server = Server(config, **kwargs)
    await server.start()
    loop = asyncio.get_running_loop()
    stop_event = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop_event.set)
        except NotImplementedError:
            pass
    serve_task = asyncio.create_task(server.serve_forever())
    await stop_event.wait()
    serve_task.cancel()
    # Graceful-shutdown cap (reference listeners/mod.rs:28: 20 s).
    try:
        await asyncio.wait_for(server.stop(), timeout=20)
    except asyncio.TimeoutError:
        pass
    finally:
        # The SIGTERM drain must flush any live jax.profiler trace even
        # when stop() hit the 20 s cap mid-way: without stop_trace the
        # PINGOO_PROFILE_DIR capture is buffered in memory and silently
        # lost on exit.
        if server.verdict is not None:
            server.verdict.ensure_trace_stopped()
            # Cost-ledger snapshot on drain (ISSUE 17): the measured
            # EWMAs are the next boot's admission costs — losing them
            # means re-seeding from BENCH_history, which is lossier.
            server.verdict.persist_cost_ledger()
        # ... and auto-dump the flight recorders (ISSUE 5): the last N
        # requests' provenance is exactly what a post-mortem of the
        # shutdown-adjacent traffic needs, and it lives only in memory.
        from ..obs.flightrecorder import dump_on_drain

        dump_on_drain("sigterm")
