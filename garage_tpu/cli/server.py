"""Node server entrypoint: `python -m garage_tpu.cli.server --config x.toml`.

Ref parity: src/garage/server.rs:30-215 (startup sequence) +
garage/main.rs. Builds the Garage root, starts RPC listen + gossip +
workers, then the S3 / admin HTTP frontends; exits cleanly on
SIGINT/SIGTERM.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal

from ..admin.rpc import AdminRpcHandler
from ..api.s3.api_server import S3ApiServer
from ..model.garage import Garage, parse_addr
from ..utils.config import read_config
from ..utils.loopprof import profiler
from ..utils.tracing import tracer

log = logging.getLogger("garage_tpu.server")


async def run_server(cfg_path: str) -> None:
    from ..utils.runtime import tune

    tune()
    cfg = await asyncio.to_thread(read_config, cfg_path)
    from ..utils import lockfile

    # held for the server's lifetime; repair-offline/convert-db take the
    # same lock, so offline maintenance can't race a live node
    lock_fd = lockfile.acquire(cfg.metadata_dir, "server")
    try:
        await _run_server_locked(cfg, cfg_path)
    finally:
        # released on EVERY exit (GL11): a failed Garage boot or
        # frontend bind must not leave the lock held when the caller
        # (tests, repair-offline in the same process) survives us
        profiler.stop()  # while the loop still runs; off unless started
        lockfile.release(lock_fd)


async def _run_server_locked(cfg, cfg_path: str) -> None:
    garage = Garage(cfg)
    if garage.block_manager.feeder.mode == "require":
        # a node that must have its device does not come up without
        # it: raises with the platform found (block/feeder.py)
        await garage.block_manager.feeder.device_verdict()
        # and meets none of its PUT or decode programs for the first
        # time inside a request (seconds each to build, and a stall of
        # every stream behind the stage thread that builds it)
        await garage.block_manager.warm_device(
            cfg.block_size, cfg.s3_ingest_buffers,
            cfg.s3_get_readahead_blocks)
    admin = AdminRpcHandler(garage)
    otlp = None
    if cfg.admin_trace_sink:
        from ..utils.otlp import setup_otlp

        otlp = setup_otlp(cfg.admin_trace_sink, garage.system.id)
    stop = asyncio.Event()

    loop = asyncio.get_event_loop()
    tracer.mark_loop_thread()  # /metrics: node_cpu_seconds{thread="loop"}
    # under GARAGE_TPU_TRACE on the main thread alone: what that thread
    # spends its CPU on (/metrics: loop_profile_*_seconds)
    profiler.start(loop)
    # SIGHUP is a shutdown signal like the reference's
    # (server.rs:185-189), not a reload; absent on some platforms
    for name in ("SIGINT", "SIGTERM", "SIGHUP"):
        sig = getattr(signal, name, None)
        if sig is None:
            continue
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass

    async def start_frontend(srv, bind: str) -> None:
        # bind addr is "host:port" or an absolute path -> Unix socket
        # (ref: util/socket_address.rs UnixOrTCPSocketAddress)
        if bind.startswith("/"):
            await srv.start(bind)
        else:
            host, port = parse_addr(bind)
            await srv.start(host, port)

    # multi-process gateway ([gateway] workers != 1 with at least one
    # TCP frontend bind): this process becomes the store node +
    # supervisor — it keeps RPC, tables, block/resync/scrub workers and
    # the admin API, while N forked workers bind the S3/K2V/web ports
    # with SO_REUSEPORT (gateway/). workers = 1 (the default) keeps the
    # single-process frontends below, byte-compatible with before.
    from ..gateway.supervisor import GatewaySupervisor, resolve_workers

    n_workers = resolve_workers(cfg.gateway.workers)
    gateway_mode = n_workers > 1 and any(
        b and not b.startswith("/")
        for b in (cfg.s3_api_bind_addr, cfg.k2v_api_bind_addr,
                  cfg.web_bind_addr))
    if n_workers > 1 and not gateway_mode:
        # same misconfiguration class GatewaySupervisor.start rejects
        # loudly for MIXED unix+TCP binds — the all-unix (or no-
        # frontend) shape must not silently run single-process while
        # the operator believes they have N workers
        log.warning(
            "[gateway] workers = %d ignored: no TCP frontend binds "
            "(SO_REUSEPORT does not apply to unix sockets); running "
            "the single-process frontend", n_workers)

    system_task = asyncio.create_task(garage.run())
    servers = []
    supervisor = None
    s3 = None
    if cfg.s3_api_bind_addr and not gateway_mode:
        s3 = S3ApiServer(garage)
        await start_frontend(s3, cfg.s3_api_bind_addr)
        servers.append(s3)
    if cfg.admin_api_bind_addr:
        from ..admin.http import AdminHttpServer

        ad = AdminHttpServer(garage, admin_rpc=admin)
        await start_frontend(ad, cfg.admin_api_bind_addr)
        servers.append(ad)
    if cfg.k2v_api_bind_addr and not gateway_mode:
        from ..api.k2v.api_server import K2VApiServer

        k2v = K2VApiServer(garage)
        await start_frontend(k2v, cfg.k2v_api_bind_addr)
        servers.append(k2v)
    if cfg.web_bind_addr and not gateway_mode:
        from ..web.server import WebServer

        web = WebServer(garage, s3)
        await start_frontend(web, cfg.web_bind_addr)
        servers.append(web)
    if gateway_mode:
        supervisor = GatewaySupervisor(garage, cfg_path,
                                       n_workers=n_workers)
        await supervisor.start()

    log.info("node %s up (rpc %s)", garage.system.id.hex()[:16],
             cfg.rpc_bind_addr)
    print(f"garage_tpu node {garage.system.id.hex()} ready", flush=True)
    await stop.wait()
    log.info("shutting down")
    if supervisor is not None:
        await supervisor.stop()
    for s in servers:
        await s.stop()
    await garage.stop()
    system_task.cancel()
    if otlp is not None:
        otlp.stop()


def main() -> None:
    p = argparse.ArgumentParser(prog="garage_tpu.cli.server")
    p.add_argument("--config", "-c",
                   default=os.environ.get("GARAGE_CONFIG_FILE",
                                          "/etc/garage.toml"))
    p.add_argument("--log-level", default=os.environ.get("RUST_LOG", "info"))
    args = p.parse_args()
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    from ..utils.lockfile import AlreadyLocked

    try:
        asyncio.run(run_server(args.config))
    except AlreadyLocked as e:
        import sys

        print(str(e), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
