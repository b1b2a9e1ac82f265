"""Dev agent: single-process server + simulated fleet + HTTP API
(reference analog: `nomad agent -dev`, command/agent/command.go:775).

Run: python -m nomad_tpu.api.devagent [--nodes N] [--port P] [--tpu]
"""
from __future__ import annotations

import argparse
import signal
import sys
import time


def start_agent(*, workers: int = 2, port: int = 4646, algorithm: str = "",
                eval_batching: bool = False, batch_width: int = 0,
                acl: bool = False, region: str = "global", join=(),
                tls=None, heartbeat_ttl: float = 0.0):
    """Start the served scheduling path -- Server (broker, workers, plan
    applier) behind the HTTP API -- wired the one way the agent serves
    it; returns ``(server, http)``, both running. ``main`` below and
    chip_smoke.py both come through here, so what the smoke proves on
    the chip is what ``python -m nomad_tpu.api.devagent`` runs.
    ``algorithm`` ("" = the default host binpack) is set before the
    server starts, because the worker pool is shaped by it (tpu-lpq
    runs ONE coalescing batch worker). ``heartbeat_ttl`` (0 = the
    server's default) is for fleets registered without a heartbeating
    client behind each node."""
    from ..server import Server
    from ..server.core import DEFAULT_HEARTBEAT_TTL
    from ..structs import SchedulerConfiguration
    from .http import HttpServer

    server = Server(num_workers=workers, acl_enabled=acl, region=region,
                    eval_batching=eval_batching,
                    batch_width=batch_width or None,
                    heartbeat_ttl=heartbeat_ttl or DEFAULT_HEARTBEAT_TTL)
    for spec in join:
        peer, _, addr = spec.partition("=")
        if peer and addr:
            server.join_federation(peer, addr)
    if algorithm:
        server.state.set_scheduler_config(SchedulerConfiguration(
            scheduler_algorithm=algorithm))
    server.start()
    # HTTP first: with port 0 the bound port is only known afterwards,
    # and real clients advertise it to workloads (attr.nomad.api_addr)
    http = HttpServer(server, port=port, tls=tls)
    http.start()
    return server, http


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="nomad-tpu dev agent")
    parser.add_argument("--nodes", type=int, default=3,
                        help="simulated client nodes")
    parser.add_argument("--port", type=int, default=4646)
    parser.add_argument("--workers", type=int, default=2)
    parser.add_argument("--tpu", action="store_true",
                        help="enable the tpu-binpack scheduler algorithm")
    parser.add_argument("--acl", action="store_true",
                        help="enable ACL enforcement (bootstrap via "
                             "POST /v1/acl/bootstrap)")
    parser.add_argument("--region", default="global")
    parser.add_argument("--join", action="append", default=[],
                        metavar="REGION=ADDR",
                        help="federate with another region's agent")
    parser.add_argument("--wan", action="store_true",
                        help="start the WAN gossip pool (regions then "
                             "discover each other via --wan-join)")
    parser.add_argument("--wan-join", action="append", default=[],
                        metavar="HOST:PORT",
                        help="join an existing WAN gossip member")
    parser.add_argument("--real-clients", action="store_true",
                        help="run full client agents with allocdirs "
                             "(enables /v1/client/fs endpoints)")
    parser.add_argument("--data-dir", default="",
                        help="client data dir (with --real-clients; "
                             "default: a temp dir)")
    parser.add_argument("--config", default="",
                        help="HCL agent config file (reference: "
                             "command/agent/config_parse.go); CLI flags "
                             "override file values")
    parser.add_argument("--eval-batching", action="store_true",
                        dest="eval_batching",
                        help="coalesce evals into fused solver dispatches")
    parser.add_argument("--batch-width", type=int, default=0,
                        dest="batch_width")
    parser.add_argument("--datacenter", default="dc1")
    # config file supplies DEFAULTS; explicitly-passed flags win
    pre, _ = parser.parse_known_args(argv)
    tls_cfg = None
    file_cfg = None
    if pre.config:
        from .config import load_agent_config
        file_cfg = load_agent_config(pre.config)
        parser.set_defaults(
            region=file_cfg.region,
            datacenter=file_cfg.datacenter,
            port=file_cfg.http_port,
            workers=file_cfg.server.workers,
            acl=file_cfg.server.acl_enabled,
            eval_batching=file_cfg.server.eval_batching,
            batch_width=file_cfg.server.batch_width,
            nodes=(file_cfg.client.simulated_nodes
                   if file_cfg.client.enabled else 0),
            real_clients=file_cfg.client.real_clients,
            data_dir=file_cfg.client.data_dir,
            tpu=(file_cfg.server.scheduler_algorithm
                 in ("tpu-binpack", "tpu-spread")))
        if file_cfg.tls.any:
            tls_cfg = file_cfg.tls
    args = parser.parse_args(argv)

    from .. import mock
    from ..client import SimClient
    from ..structs import SCHED_ALG_TPU_BINPACK

    server, http = start_agent(
        workers=args.workers, port=args.port,
        algorithm=SCHED_ALG_TPU_BINPACK if args.tpu else "",
        eval_batching=args.eval_batching, batch_width=args.batch_width,
        acl=args.acl, region=args.region, join=args.join, tls=tls_cfg)
    scheme = ("https" if tls_cfg is not None and tls_cfg.enable_http
              else "http")
    clients = []
    if args.real_clients:
        import os
        import tempfile
        from ..client.client import Client, LocalServerConn
        base = args.data_dir or tempfile.mkdtemp(prefix="nomad-tpu-dev-")
        for i in range(args.nodes):
            c = Client(LocalServerConn(server),
                       os.path.join(base, f"client{i}"),
                       name=f"dev-client-{i}",
                       api_addr=f"{scheme}://127.0.0.1:{http.port}",
                       serve_http=True)
            c.start()
            clients.append(c)
            http.add_client(c)
    else:
        for _ in range(args.nodes):
            c = SimClient(server, mock.node(datacenter=args.datacenter))
            c.start()
            clients.append(c)
    statsd = None
    if file_cfg is not None and file_cfg.telemetry.statsd_address:
        from ..server.telemetry import StatsdSink, metrics as _metrics
        statsd = StatsdSink(file_cfg.telemetry.statsd_address, _metrics,
                            interval_s=file_cfg.telemetry.interval_s)
        statsd.start()
        print(f"==> statsd sink: {file_cfg.telemetry.statsd_address}")
    if args.wan or args.wan_join:
        wan = server.enable_wan(f"{scheme}://127.0.0.1:{http.port}",
                                name=args.region)
        for spec in args.wan_join:
            host, _, port = spec.rpartition(":")
            if not port.isdigit():
                parser.error(f"--wan-join needs HOST:PORT, got {spec!r}")
            server.wan_join((host or "127.0.0.1", int(port)))
        print(f"==> WAN gossip: {wan.addr[0]}:{wan.addr[1]}")
    print(f"==> nomad-tpu dev agent: {scheme}://127.0.0.1:{http.port} "
          f"({args.nodes} simulated nodes, "
          f"algorithm={server.state.scheduler_config().scheduler_algorithm})")

    stop = []
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        if statsd is not None:
            statsd.shutdown()
        http.shutdown()
        for c in clients:
            (c.stop if hasattr(c, "stop") else c.shutdown)()
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
