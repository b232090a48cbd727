"""CLI entrypoint: `python -m pingoo_tpu [--config PATH]`.

Reference parity (pingoo/main.rs:33-85): logging init -> config load ->
shutdown signal watch -> optional child process (sidecar mode,
main.rs:60-80) -> server run. The reference takes no CLI flags and uses
fixed /etc/pingoo paths; we accept overrides for testability but default
to the same locations.
"""

from __future__ import annotations

import argparse
import asyncio
import subprocess
import sys

from .config import DEFAULT_CONFIG_FILE, ConfigError, load_and_validate
from .host.captcha import DEFAULT_JWKS_PATH
from .logging_utils import get_logger, init_logging

log = get_logger("pingoo_tpu")

# What a deployment's file may demand of the build that serves it
# (`--require`): a build without the capability refuses the command
# line instead of serving the deployment without it.
#   listener-metrics: /__pingoo/metrics on a listener's port answers for
#   all of its --native-workers, whichever of them takes the scrape.
CAPABILITIES = ("listener-metrics",)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="pingoo-tpu")
    parser.add_argument("--config", default=DEFAULT_CONFIG_FILE)
    parser.add_argument("--no-device", action="store_true",
                        help="CPU-interpreter rules engine only")
    parser.add_argument("--no-docker", action="store_true")
    parser.add_argument("--cache-dir", default=None,
                        help="compiled-ruleset artifact cache directory")
    parser.add_argument("--bot-score-params", default=None,
                        help="npz of trained bot-score head weights "
                             "(models/botscore.save_params)")
    parser.add_argument("--native-plane", action="store_true",
                        help="front traffic with the C++ data plane "
                             "(epoll httpd + shared-memory verdict ring); "
                             "the Python plane moves to loopback as the "
                             "captcha/fail-open target")
    parser.add_argument("--native-workers", type=int, default=1,
                        help="SO_REUSEPORT httpd workers per listener "
                             "(one verdict ring each)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="chips the native plane's drain loop launches "
                             "whole batches on, each holding a copy of "
                             "the rules and lists (the first N local "
                             "devices; not with a PINGOO_MESH over "
                             "several)")
    parser.add_argument("--require", action="append", default=[],
                        choices=CAPABILITIES, metavar="CAPABILITY",
                        help="refuse to start unless this build has the "
                             "capability (may repeat): "
                             + ", ".join(CAPABILITIES))
    parser.add_argument("--state-dir", default="/var/run/pingoo",
                        help="ring files + services table directory "
                             "(native plane)")
    parser.add_argument("--upstream-ca", default=None,
                        help="PEM trust bundle for TLS upstream hops "
                             "(native plane; system roots by default)")
    parser.add_argument("--captcha-jwks", default=DEFAULT_JWKS_PATH,
                        help="captcha signing-key JWKS file (created on "
                             "first boot)")
    args = parser.parse_args(argv)

    init_logging()
    try:
        config = load_and_validate(args.config)
    except ConfigError as exc:
        log.error(str(exc))
        return 1

    # Backend selection: JAX decides, once, in this process. --no-device
    # pins the CPU before first use; otherwise backend_info() takes what
    # jax.devices() gives and lets JAX's own error fail the boot — no
    # probe child, no silent CPU pin (pingoo_tpu/backend.py). The
    # compile cache is placed before anything jits.
    from .backend import backend_info, force_cpu_backend, place_compile_cache

    if args.no_device:
        force_cpu_backend()
    compile_cache = place_compile_cache()
    try:
        backend = backend_info()
    except RuntimeError as exc:
        log.error(f"jax backend initialisation failed: {exc}")
        return 1
    if args.replicas != 1:
        from .native_ring import replica_devices

        try:
            if not args.native_plane or args.no_device:
                raise ValueError(f"--replicas {args.replicas} needs "
                                 "--native-plane and the device")
            replica_devices(args.replicas)
        except ValueError as exc:
            log.error(str(exc))
            return 2

    child = None
    if config.child_process is not None:
        # Sidecar mode: run the fronted app as a child (main.rs:60-80).
        child = subprocess.Popen(list(config.child_process.command))
        log.info("child process started",
                 extra={"fields": {"pid": child.pid}})

    log.info("starting pingoo-tpu", extra={"fields": {
        "config": args.config,
        "listeners": [f"{l.protocol.value}://{l.host}:{l.port}"
                      for l in config.listeners],
        "rules": len(config.rules),
        "device": not args.no_device,
        "native_plane": args.native_plane,
        "replicas": args.replicas,
        "capabilities": list(CAPABILITIES),
        **backend,
        "compile_cache": compile_cache,
    }})
    try:
        if args.native_plane:
            from .host.native_plane import run_native

            asyncio.run(run_native(
                config, state_dir=args.state_dir,
                workers=args.native_workers,
                replicas=args.replicas,
                upstream_ca=args.upstream_ca,
                use_device=not args.no_device,
                enable_docker=not args.no_docker,
                cache_dir=args.cache_dir,
                captcha_jwks_path=args.captcha_jwks,
                bot_score_params_path=args.bot_score_params))
        else:
            from .host.server import run

            asyncio.run(run(config, use_device=not args.no_device,
                            enable_docker=not args.no_docker,
                            cache_dir=args.cache_dir,
                            captcha_jwks_path=args.captcha_jwks,
                            bot_score_params_path=args.bot_score_params))
    except KeyboardInterrupt:
        pass
    finally:
        if child is not None:
            child.terminate()
    return 0


if __name__ == "__main__":
    sys.exit(main())
