"""greptime CLI: option loading (TOML + flags) and server lifecycle.

Reference behavior: src/cmd — `greptime standalone start -c config.toml
--http-addr ...`; flags override file options (src/cmd/src/options.rs).
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class StandaloneOptions:
    data_home: str = "./greptimedb_data"
    http_addr: str = "127.0.0.1:4000"
    mysql_addr: str = "127.0.0.1:4002"
    postgres_addr: str = "127.0.0.1:4003"
    grpc_addr: str = "127.0.0.1:4001"
    #: OpenTSDB telnet `put` listener; empty/None = disabled (reference
    #: serves it on 4242, src/servers/src/opentsdb.rs:60)
    opentsdb_addr: Optional[str] = None
    user_provider: Optional[str] = None
    enable_mysql: bool = True
    enable_postgres: bool = True
    enable_grpc: bool = True
    log_level: str = "info"
    #: [storage] table from the TOML: type=File|S3, bucket, endpoint,
    #: cache_path... (reference: ObjectStoreConfig, datanode.rs:126-204)
    storage: dict = field(default_factory=dict)
    #: [tls] table: mode=disable|prefer|require + cert/key paths
    #: (reference: TlsOption, servers/src/tls.rs)
    tls: dict = field(default_factory=dict)
    #: [query] table: stream_threshold_rows / stream_slice_rows (cold-scan
    #: streaming), cold_reduce ("host"/"device" partial reduction),
    #: scan_cache_budget_mb (device scan cache bound)
    query: dict = field(default_factory=dict)
    log_dir: Optional[str] = None
    #: [logging] otlp_endpoint: OTLP/HTTP collector base URL (spans are
    #: exported to {endpoint}/v1/traces when set)
    otlp_endpoint: Optional[str] = None
    #: --wal-sync-on-write: a write is acknowledged after the WAL's fsync
    #: (upstream's `[wal] sync_write = true`)
    wal_sync_on_write: bool = False


def load_options(args) -> StandaloneOptions:
    opts = StandaloneOptions()
    if getattr(args, "config_file", None):
        import tomllib
        with open(args.config_file, "rb") as f:
            doc = tomllib.load(f)
        opts.storage = doc.get("storage", {})
        opts.data_home = opts.storage.get("data_home", opts.data_home)
        http = doc.get("http", {})
        opts.http_addr = http.get("addr", opts.http_addr)
        mysql = doc.get("mysql", {})
        opts.mysql_addr = mysql.get("addr", opts.mysql_addr)
        opts.enable_mysql = mysql.get("enable", True)
        pg = doc.get("postgres", {})
        opts.postgres_addr = pg.get("addr", opts.postgres_addr)
        opts.enable_postgres = pg.get("enable", True)
        grpc = doc.get("grpc", {})
        opts.grpc_addr = grpc.get("addr", opts.grpc_addr)
        opts.enable_grpc = grpc.get("enable", True)
        tsdb = doc.get("opentsdb", {})
        if tsdb.get("enable", False):
            opts.opentsdb_addr = tsdb.get("addr", "127.0.0.1:4242")
        logging_doc = doc.get("logging", {})
        opts.log_level = logging_doc.get("level", opts.log_level)
        opts.log_dir = logging_doc.get("dir", opts.log_dir)
        opts.otlp_endpoint = logging_doc.get("otlp_endpoint",
                                             opts.otlp_endpoint)
        opts.tls = doc.get("tls", {})
        opts.query = doc.get("query", {})
    for name in ("data_home", "http_addr", "mysql_addr", "postgres_addr",
                 "grpc_addr", "opentsdb_addr", "user_provider"):
        v = getattr(args, name, None)
        if v is not None:
            setattr(opts, name, v)
    opts.wal_sync_on_write = bool(getattr(args, "wal_sync_on_write", False))
    return opts


def build_servers(opts: StandaloneOptions):
    """Compose standalone frontend + protocol servers (not yet started)."""
    from ..datanode import DatanodeInstance, DatanodeOptions
    from ..frontend import FrontendInstance
    from ..servers.auth import NoopUserProvider, StaticUserProvider
    from ..servers.http import HttpServer

    if opts.query:
        from ..query.stream_exec import configure_streaming
        configure_streaming(
            threshold_rows=opts.query.get("stream_threshold_rows"),
            slice_rows=opts.query.get("stream_slice_rows"),
            cold_reduce=opts.query.get("cold_reduce"))
        budget_mb = opts.query.get("scan_cache_budget_mb")
        if budget_mb is not None:
            from ..storage import scan_cache
            scan_cache.SCAN_CACHE.configure(
                budget_bytes=int(budget_mb) << 20)
    store = None
    if opts.storage and str(opts.storage.get("type", "File")) != "File":
        from ..storage.object_store import build_object_store
        store = build_object_store(opts.storage, opts.data_home)
    dn = DatanodeInstance(DatanodeOptions(
        data_home=opts.data_home,
        wal_sync_on_write=opts.wal_sync_on_write), store=store)
    fe = FrontendInstance(dn)
    fe.start()
    provider = NoopUserProvider()
    if opts.user_provider:
        provider = StaticUserProvider.from_option(opts.user_provider)
    def split_addr(addr):
        host, _, port = addr.partition(":")
        return host or "127.0.0.1", int(port or 0)

    ssl_context = None
    if opts.tls:
        from ..servers.tls import TlsOption
        ssl_context = TlsOption.from_config(opts.tls).setup()
    servers = [HttpServer(fe, provider, opts.http_addr,
                          ssl_context=ssl_context)]
    if opts.enable_mysql:
        from ..servers.mysql import MysqlServer
        host, port = split_addr(opts.mysql_addr)
        servers.append(MysqlServer(fe, host=host, port=port,
                                   user_provider=provider,
                                   ssl_context=ssl_context))
    if opts.enable_postgres:
        from ..servers.postgres import PostgresServer
        host, port = split_addr(opts.postgres_addr)
        servers.append(PostgresServer(fe, host=host, port=port,
                                      user_provider=provider,
                                      ssl_context=ssl_context))
    if opts.enable_grpc:
        from ..servers.grpc import GrpcServer
        servers.append(GrpcServer(fe, provider, opts.grpc_addr))
    if opts.opentsdb_addr:
        from ..servers.opentsdb import OpentsdbServer
        host, port = split_addr(opts.opentsdb_addr)
        servers.append(OpentsdbServer(fe, host=host, port=port))
    return fe, servers


def standalone_start(args) -> None:
    opts = load_options(args)
    from ..common.telemetry import (configure_otlp, init_logging,
                                    install_gc_timer, install_panic_hook)
    init_logging(opts.log_level, opts.log_dir)
    if opts.otlp_endpoint:
        configure_otlp(opts.otlp_endpoint, service_name="greptimedb")
    install_panic_hook()
    install_gc_timer()
    _pin_allocator()
    _claim_device()
    fe, servers = build_servers(opts)
    for s in servers:
        s.start()
        logging.info("started %s on %s:%s", type(s).__name__,
                     getattr(s, "host", "?"), getattr(s, "port", "?"))
    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    logging.info("greptimedb_tpu standalone ready (data_home=%s)",
                 opts.data_home)
    stop.wait()
    for s in servers:
        s.shutdown()
    fe.shutdown()


def _pin_allocator() -> None:
    """The roles that hold tables: malloc's thresholds fixed before the
    device is claimed (`common/runtime.py:pin_allocator`), and said in
    the log."""
    from ..common.runtime import pin_allocator
    pinned = pin_allocator()
    logging.info("allocator: %s", "as the environment has it"
                 if pinned is None else ", ".join(
                     f"{k}={v >> 20} MiB" for k, v in pinned.items()))


def _claim_device() -> None:
    """Chip-owning roles: place the compile cache, bring the backend up
    and log what this process runs on (exits when that is a CPU nobody
    asked for)."""
    from ..common.device import require_device
    from ..common.jax_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = require_device()
    logging.info("device: platform=%s device_kind=%s device_count=%d "
                 "(compile cache %s)", dev["platform"],
                 dev["device_kind"], dev["device_count"], cache_dir)


def _block_until_signal(on_shutdown) -> None:
    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    stop.wait()
    on_shutdown()


def _meta_client(addr_arg: str):
    """--metasrv-addr accepts a comma-separated replica list; the
    failover client walks it until a leader answers."""
    from ..meta.flight import FailoverFlightMetaClient
    addrs = [a.strip() for a in addr_arg.split(",") if a.strip()]
    return FailoverFlightMetaClient([f"grpc://{a}" for a in addrs])


def metasrv_start(args) -> None:
    """Run the metadata server role (reference: greptime metasrv start;
    etcd is replaced by a file-backed KV snapshot)."""
    from ..common.telemetry import init_logging
    from ..meta import MetaSrv
    from ..meta.flight import FlightMetaServer
    from ..meta.kv import FileKv, MemKv

    init_logging(args.log_level or "info")
    from ..common import background_jobs, trace_store
    background_jobs.configure_node("metasrv")
    # buffer-role sink: balancer-op traces root HERE and verdict
    # locally (always retained — the balancer tail rule); retained
    # spans ride home on the next meta RPC's response and the caller
    # writes them into greptime_private.trace_spans
    trace_store.install(trace_store.TraceSink(
        node_label="metasrv", service="metasrv", role="buffer"))
    raft_node = None
    if args.peers:
        # replicated meta: --peers is the FULL replica set (including
        # this node) and must be IDENTICAL on every node — raft ids come
        # from its sorted order, so a divergent list (extra/missing
        # entry, different host spelling) would misattribute votes.
        # Routes survive a metasrv loss (reference: etcd cluster,
        # store/etcd.rs:762); transports ride the same Flight plane.
        from ..meta.replication import (
            FlightTransport, RaftNode, ReplicatedKv)
        peers = sorted({a.strip() for a in args.peers.split(",")
                        if a.strip()})
        if args.bind_addr not in peers:
            raise SystemExit(
                f"--peers must list every replica including this node's "
                f"--bind-addr {args.bind_addr!r} verbatim; got {peers}")
        peer_addrs = dict(enumerate(peers, start=1))
        my_id = next(i for i, a in peer_addrs.items()
                     if a == args.bind_addr)
        raft_node = RaftNode(
            my_id, list(peer_addrs),
            store_path=f"{args.store}.raft" if args.store else None)
        for pid, addr in peer_addrs.items():
            if pid != my_id:
                raft_node.transports[pid] = FlightTransport(
                    f"grpc://{addr}")
        kv = ReplicatedKv(raft_node)
    else:
        kv = FileKv(args.store) if args.store else MemKv()
    srv = MetaSrv(kv, datanode_lease_secs=args.datanode_lease_secs)
    server = FlightMetaServer(srv, f"grpc://{args.bind_addr}",
                              raft_node=raft_node)
    server.serve_in_background()
    if raft_node is not None:
        raft_node.start()
    # leader election: with several metasrv replicas over one KV, only
    # the lease holder mutates routes (reference: election/etcd.rs).
    # Under raft the consensus leader IS the lease holder.
    if raft_node is not None:
        class _RaftElection:
            def start(self):
                pass

            def stop(self):
                pass

            @property
            def is_leader(self):
                return raft_node.is_leader
        election = _RaftElection()
    else:
        from ..meta.lock import Election
        election = Election(kv, f"metasrv-{args.bind_addr}")
    election.start()

    # region failover runner (reference: FailureDetectRunner on the
    # leader; the action itself is this build's upgrade over v0.2) plus
    # the elastic-region balancer control loop (split/migrate/rebalance
    # state machines resume from the __balancer/ KV keys on restart)
    from ..common.runtime import RepeatedTask
    srv.balancer.is_leader_fn = lambda: election.is_leader

    def failover_tick():
        if not election.is_leader:
            return
        moves = srv.failover_check()
        for m in moves:
            logging.warning("failover: region %s of %s moved %d -> %d",
                            m["region"], m["table"], m["from"], m["to"])
        srv.balancer.tick()

    runner = RepeatedTask(args.failover_interval, failover_tick,
                          name="failover-runner")
    runner.start()
    logging.info("metasrv ready on %s (leader=%s)", server.address,
                 election.is_leader)

    def shutdown():
        runner.stop()
        election.stop()
        if raft_node is not None:
            raft_node.stop()
        server.shutdown()

    _block_until_signal(shutdown)


def datanode_start(args) -> None:
    """Run a region-hosting worker: Flight data plane + meta heartbeats
    (reference: greptime datanode start)."""
    from ..common.telemetry import init_logging
    from ..datanode import DatanodeInstance, DatanodeOptions
    from ..meta import Peer
    from ..meta.flight import FlightMetaClient
    from ..servers.flight import FlightDatanodeServer

    init_logging(args.log_level or "info")
    _pin_allocator()
    _claim_device()
    # buffer-role trace sink: this process cannot decide tail-sampling
    # verdicts (it sees only its fragments of each trace) and cannot
    # write trace_spans — it buffers spans keyed by trace_id until the
    # frontend's verdict piggybacks on a later RPC, then ships released
    # spans home on that RPC's response (TTL evicts the unclaimed)
    from ..common import background_jobs, profiler, trace_store
    label = f"dn{args.node_id}"
    background_jobs.configure_node(label)
    trace_store.install(trace_store.TraceSink(
        node_label=label, service="datanode", role="buffer"))
    # writer-less sampler: this process cannot write profile_samples;
    # its folded stacks drain over the Flight `profile` action to the
    # asking frontend, which absorbs and writes them
    profiler.install(profiler.Profiler(node_label=label))
    dn = DatanodeInstance(DatanodeOptions(
        data_home=args.data_home or "./greptimedb_data",
        node_id=args.node_id, register_numbers_table=False,
        wal_sync_on_write=bool(getattr(args, "wal_sync_on_write",
                                       False))))
    dn.start()
    server = FlightDatanodeServer(dn, f"grpc://{args.rpc_addr}")
    server.serve_in_background()
    meta = _meta_client(args.metasrv_addr)
    meta.register(Peer(args.node_id, server.address))
    dn.start_heartbeat(meta, interval_s=args.heartbeat_interval)
    logging.info("datanode %d ready on %s (meta %s)", args.node_id,
                 server.address, args.metasrv_addr)

    def shutdown():
        server.shutdown()
        dn.shutdown()
        meta.close()

    _block_until_signal(shutdown)


def frontend_start(args) -> None:
    """Run the stateless router role: SQL over HTTP/MySQL/Postgres/Flight
    against datanodes resolved through the meta service (reference:
    greptime frontend start)."""
    from ..common.telemetry import init_logging
    from ..frontend.distributed import DistInstance
    from ..meta.flight import FlightMetaClient, PeerClientRegistry
    from ..servers.flight import FlightFrontendServer
    from ..servers.http import HttpServer
    from ..servers.auth import NoopUserProvider

    init_logging(args.log_level or "info")
    meta = _meta_client(args.metasrv_addr)
    clients = PeerClientRegistry(meta)
    fe = DistInstance(meta, clients)
    # self-monitoring scrape loop: frontend registry + cluster-wide
    # region heat (meta heartbeats) → greptime_private tables
    from ..common.runtime import env_int
    monitor_interval = env_int("GREPTIME_SELF_MONITOR_INTERVAL_S", 30)
    if monitor_interval > 0:
        fe.self_monitor.start_background(monitor_interval)
    servers = [HttpServer(fe, NoopUserProvider(), args.http_addr)]
    if args.mysql_addr:
        from ..servers.mysql import MysqlServer
        host, _, port = args.mysql_addr.partition(":")
        servers.append(MysqlServer(fe, host=host or "127.0.0.1",
                                   port=int(port or 0)))
    if args.postgres_addr:
        from ..servers.postgres import PostgresServer
        host, _, port = args.postgres_addr.partition(":")
        servers.append(PostgresServer(fe, host=host or "127.0.0.1",
                                      port=int(port or 0)))
    if args.grpc_addr:
        servers.append(FlightFrontendServer(fe,
                                            f"grpc://{args.grpc_addr}"))
    for s in servers:
        s.serve_in_background() if hasattr(s, "serve_in_background")             else s.start()
    logging.info("frontend ready (http %s, meta %s)", args.http_addr,
                 args.metasrv_addr)

    def shutdown():
        fe.self_monitor.stop()
        for s in servers:
            s.shutdown()
        meta.close()

    _block_until_signal(shutdown)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="greptime", description="greptimedb_tpu CLI")
    sub = parser.add_subparsers(dest="subcommand", required=True)

    standalone = sub.add_parser("standalone")
    ssub = standalone.add_subparsers(dest="action", required=True)
    start = ssub.add_parser("start")
    start.add_argument("-c", "--config-file")
    start.add_argument("--data-home")
    start.add_argument("--http-addr")
    start.add_argument("--mysql-addr")
    start.add_argument("--postgres-addr")
    start.add_argument("--grpc-addr")
    start.add_argument("--opentsdb-addr")
    start.add_argument("--user-provider")
    start.add_argument("--wal-sync-on-write", action="store_true",
                       help="fsync the WAL before acking each write")
    start.set_defaults(func=standalone_start)

    metasrv = sub.add_parser("metasrv")
    msub = metasrv.add_subparsers(dest="action", required=True)
    mstart = msub.add_parser("start")
    mstart.add_argument("--bind-addr", default="127.0.0.1:3002")
    mstart.add_argument("--store", help="path for the file-backed KV")
    mstart.add_argument("--peers", help="comma-separated bind addrs of "
                        "the full metasrv replica set (enables the "
                        "replicated raft store)")
    mstart.add_argument("--failover-interval", type=float, default=10.0)
    mstart.add_argument("--datanode-lease-secs", type=float, default=15.0)
    mstart.add_argument("--log-level")
    mstart.set_defaults(func=metasrv_start)

    datanode = sub.add_parser("datanode")
    dsub = datanode.add_subparsers(dest="action", required=True)
    dstart = dsub.add_parser("start")
    dstart.add_argument("--node-id", type=int, required=True)
    dstart.add_argument("--rpc-addr", default="127.0.0.1:0")
    dstart.add_argument("--metasrv-addr", default="127.0.0.1:3002")
    dstart.add_argument("--data-home")
    dstart.add_argument("--heartbeat-interval", type=float, default=5.0)
    dstart.add_argument("--wal-sync-on-write", action="store_true",
                        help="fsync the WAL before acking each write "
                             "(the replication acceptance drives run "
                             "with this on)")
    dstart.add_argument("--log-level")
    dstart.set_defaults(func=datanode_start)

    frontend = sub.add_parser("frontend")
    fsub = frontend.add_subparsers(dest="action", required=True)
    fstart = fsub.add_parser("start")
    fstart.add_argument("--metasrv-addr", default="127.0.0.1:3002")
    fstart.add_argument("--http-addr", default="127.0.0.1:4000")
    fstart.add_argument("--mysql-addr")
    fstart.add_argument("--postgres-addr")
    fstart.add_argument("--grpc-addr")
    fstart.add_argument("--log-level")
    fstart.set_defaults(func=frontend_start)

    cli = sub.add_parser("cli")
    csub = cli.add_subparsers(dest="action", required=True)
    attach = csub.add_parser("attach")
    attach.add_argument("--grpc-addr", default="127.0.0.1:4001")
    attach.set_defaults(func=_cli_attach)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    args.func(args)
    return 0


def _cli_attach(args) -> None:
    """Interactive SQL REPL over the Flight/gRPC client."""
    from ..client.flight import Database
    from ..datatypes.record_batch import pretty_print
    addr = args.grpc_addr
    if "://" not in addr:
        addr = f"grpc://{addr}"
    db = Database(addr)
    print("greptimedb_tpu REPL — end statements with ';', \\q to quit")
    buf = []
    while True:
        try:
            line = input("> " if not buf else "… ")
        except EOFError:
            break
        if line.strip() in ("\\q", "exit", "quit"):
            break
        buf.append(line)
        if line.rstrip().endswith(";"):
            sql = "\n".join(buf)
            buf = []
            try:
                out = db.sql(sql)
                if isinstance(out, int):
                    print(f"Affected Rows: {out}")
                else:
                    print(pretty_print(out))
            except Exception as e:  # noqa: BLE001
                print(f"error: {e}")


if __name__ == "__main__":
    sys.exit(main())
