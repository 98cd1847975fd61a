"""The standard multi-process control-plane topology on the Supervisor.

One ``Cluster`` = one apiserver + N scheduler replicas (+ optional
collector and M watch-fanout driver processes), each a real OS process
spawned from this interpreter's ``python -m kubetpu`` entry points, wired
together through readiness banners (nobody pre-picks a port):

    collector?  ──►  apiserver  ──►  scheduler r0..r{N-1}  ──►  drivers

``kubetpu up`` serves this topology interactively and the tier-1
multi-process smoke drives it; both go through the same ChildSpec
builders, so they exercise ONE spawn/readiness/shutdown path.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field

from .supervisor import Child, ChildSpec, Supervisor


def kubetpu_argv(*args: str, python: str | None = None) -> list[str]:
    """argv for a ``kubetpu`` subcommand run by THIS interpreter — the
    children run the same build as the supervisor (the cross-process
    schema fingerprint makes a drifted build refuse loudly anyway)."""
    return [python or sys.executable, "-m", "kubetpu", *args]


def apiserver_spec(
    *,
    name: str = "apiserver",
    wire: str = "binary",
    persistence: str | None = None,
    telemetry: str = "off",
    restart: str = "never",
    env: dict | None = None,
    ready_timeout_s: float = 120.0,
    port: int = 0,
    replicated: bool = False,
    follow: str = "",
    peers: tuple = (),
    replica_index: int = 0,
    lease_duration_s: float = 0.0,
    replicate_from: str = "",
) -> ChildSpec:
    """``replicated``/``follow``/``peers``: the replicated read plane —
    a leader spec sets ``replicated=True`` (holds the writer lease), a
    follower spec sets ``follow=<leader url>``; both carry the full
    ``peers`` electorate for failover. ``replicate_from`` chains this
    follower's tail off another follower's re-served feed (leader egress
    stays O(direct fan-out)). All default OFF: the unreplicated spec's
    argv is byte-identical to what it always was."""
    args = ["apiserver", "--port", str(port), "--wire", wire]
    if persistence:
        args += ["--persistence", persistence]
    if telemetry and telemetry != "off":
        args += ["--telemetry", telemetry]
    if replicated and not follow:
        args += ["--replicated"]
    if follow:
        args += ["--follow", follow]
    if peers:
        args += ["--peers", ",".join(peers)]
    if replica_index:
        args += ["--replica-index", str(replica_index)]
    if lease_duration_s:
        args += ["--lease-duration", str(lease_duration_s)]
    if replicate_from:
        args += ["--replicate-from", replicate_from]
    return ChildSpec(
        name=name, argv=kubetpu_argv(*args), restart=restart,
        env=env, shutdown_phase=1, ready_timeout_s=ready_timeout_s,
    )


def collector_spec(
    *, name: str = "collector", env: dict | None = None,
    ready_timeout_s: float = 60.0,
) -> ChildSpec:
    return ChildSpec(
        name=name, argv=kubetpu_argv("collector", "--port", "0"),
        env=env, shutdown_phase=1, ready_timeout_s=ready_timeout_s,
    )


def scheduler_spec(
    *,
    name: str,
    server: str,
    replica_id: str = "",
    partition: str = "",
    replica_count: int = 0,
    partitions: int = 0,
    wire: str = "binary",
    engine: str = "greedy",
    topology: str = "off",
    max_batch: int = 0,
    telemetry: str = "off",
    prewarm: bool = False,
    diagnostics: str = "ephemeral",
    restart: str = "never",
    env: dict | None = None,
    ready_timeout_s: float = 180.0,
    extra_args: tuple = (),
) -> ChildSpec:
    args = [
        "scheduler", "--server", server, "--engine", engine,
        "--wire", wire, "--diagnostics-port", diagnostics,
    ]
    if replica_id:
        args += ["--replica-id", replica_id]
    if partition:
        args += ["--partition", partition]
    if replica_count:
        args += ["--replica-count", str(replica_count)]
    if partitions:
        args += ["--partitions", str(partitions)]
    if topology and topology != "off":
        args += ["--topology", topology]
    if max_batch:
        args += ["--max-batch", str(max_batch)]
    if telemetry and telemetry != "off":
        args += ["--telemetry", telemetry]
    if prewarm:
        args += ["--prewarm"]
    args += list(extra_args)
    return ChildSpec(
        name=name, argv=kubetpu_argv(*args), restart=restart,
        env=env, shutdown_phase=0, ready_timeout_s=ready_timeout_s,
    )


def watch_driver_spec(
    *,
    name: str,
    server: str,
    watchers: int,
    wire: str = "binary",
    env: dict | None = None,
    ready_timeout_s: float = 60.0,
) -> ChildSpec:
    return ChildSpec(
        name=name,
        argv=kubetpu_argv(
            "watch-driver", "--server", server,
            "--watchers", str(watchers), "--wire", wire,
        ),
        env=env, shutdown_phase=0, ready_timeout_s=ready_timeout_s,
    )


@dataclass
class Cluster:
    """See module docstring. ``telemetry``: "off" | "embed" (collector ON
    the apiserver, schedulers export to it) | "collector" (a spawned
    collector child) | a collector URL. ``fanout_watchers`` total watchers
    are spread over ``fanout_procs`` driver processes."""

    replicas: int = 1
    apiservers: int = 1
    #: writer-lease duration handed to a REPLICATED plane's apiservers
    #: (0 = the CLI default). A failover test tunes this down so that it
    #: exercises the protocol, not a lazy lease expiry.
    lease_duration_s: float = 0.0
    #: chained replication shipping: follower i>1 tails follower i-1's
    #: re-served feed instead of the leader (leader ships ONE stream; a
    #: dead/stale link falls its downstream back to the leader). False =
    #: the PR-17 star (every follower tails the leader directly).
    replication_chain: bool = False
    partition: str = "race"
    wire: str = "binary"
    engine: str = "greedy"
    topology: str = "off"
    max_batch: int = 0
    persistence: str | None = None
    telemetry: str = "off"
    fanout_procs: int = 0
    fanout_watchers: int = 0
    restart: str = "on-failure:2"
    prewarm: bool = False
    env: dict | None = None
    cwd: str | None = None
    ready_timeout_s: float = 180.0

    supervisor: Supervisor = field(init=False, default=None)
    schedulers: list = field(init=False, default_factory=list)
    drivers: list = field(init=False, default_factory=list)
    apiserver_children: list = field(init=False, default_factory=list)
    api_url: str = field(init=False, default="")
    api_urls: list = field(init=False, default_factory=list)
    collector_url: str = field(init=False, default="")

    def start(self) -> "Cluster":
        self.supervisor = Supervisor(env=self.env, cwd=self.cwd)
        try:
            self._start_children()
        except BaseException:
            self.supervisor.shutdown()
            raise
        self.supervisor.start_monitor()
        return self

    def _start_children(self) -> None:
        sup = self.supervisor
        api_telemetry = self.telemetry
        if self.telemetry == "collector":
            coll = sup.spawn(collector_spec(env=self.env))
            self.collector_url = coll.url()
            api_telemetry = self.collector_url
        if self.apiservers > 1:
            self._start_apiservers(sup, api_telemetry)
        else:
            # the single-apiserver path is UNTOUCHED: same spec, same
            # argv, byte-for-byte (the --apiservers 1 escape hatch)
            api = sup.spawn(apiserver_spec(
                wire=self.wire, persistence=self.persistence,
                telemetry=api_telemetry, env=self.env,
                ready_timeout_s=self.ready_timeout_s,
            ))
            self.api_url = api.url()
            self.api_urls = [self.api_url]
            self.apiserver_children = [api]
        if self.telemetry == "embed":
            # the embedded collector serves on the apiserver's own port
            self.collector_url = self.api_url
        sched_telemetry = self.collector_url or (
            self.telemetry if self.telemetry.startswith("http") else ""
        )
        for i in range(self.replicas):
            rid = f"r{i}"
            self.schedulers.append(sup.spawn(scheduler_spec(
                name=f"scheduler-{rid}", server=self.api_url,
                replica_id=rid, partition=self.partition,
                replica_count=self.replicas,
                wire=self.wire, engine=self.engine,
                topology=self.topology,
                max_batch=self.max_batch,
                telemetry=sched_telemetry or "off",
                prewarm=self.prewarm, restart=self.restart, env=self.env,
                ready_timeout_s=self.ready_timeout_s,
            )))
        procs = self.fanout_procs or (1 if self.fanout_watchers else 0)
        if procs and self.fanout_watchers:
            # watch fan-out is the READ load — with followers present it
            # round-robins over them, leaving the leader to its writers
            read_urls = self.api_urls[1:] or [self.api_url]
            per = -(-self.fanout_watchers // procs)               # ceil
            left = self.fanout_watchers
            for i in range(procs):
                n = min(per, left)
                left -= n
                if n <= 0:
                    break
                self.drivers.append(sup.spawn(watch_driver_spec(
                    name=f"watch-driver-{i}",
                    server=read_urls[i % len(read_urls)],
                    watchers=n, wire=self.wire, env=self.env,
                )))

    def _start_apiservers(self, sup, api_telemetry: str) -> None:
        """The replicated read plane: one leader + N-1 followers. Ports
        are pre-allocated (bind 0 → read → close) so every child can be
        handed the FULL peer electorate up front — followers need it for
        failover elections, and the leader's URL must be printable in a
        follower's argv before the leader has bannered."""
        import socket

        ports = []
        socks = []
        try:
            for _ in range(self.apiservers):
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
                socks.append(s)
        finally:
            for s in socks:
                s.close()
        peer_urls = [f"http://127.0.0.1:{p}" for p in ports]
        leader_url = peer_urls[0]
        children = [sup.spawn(apiserver_spec(
            name="apiserver", port=ports[0], wire=self.wire,
            persistence=self.persistence, telemetry=api_telemetry,
            replicated=True, peers=tuple(peer_urls),
            lease_duration_s=self.lease_duration_s,
            env=self.env, ready_timeout_s=self.ready_timeout_s,
        ))]
        for i in range(1, self.apiservers):
            # followers never persist — their WAL is the leader's
            children.append(sup.spawn(apiserver_spec(
                name=f"apiserver-f{i}", port=ports[i], wire=self.wire,
                telemetry="off", follow=leader_url,
                peers=tuple(peer_urls), replica_index=i,
                lease_duration_s=self.lease_duration_s,
                # linear chain: f1 tails the leader, f2 tails f1, … —
                # the leader's replication egress is one follower's worth
                replicate_from=(
                    peer_urls[i - 1] if self.replication_chain and i > 1
                    else ""
                ),
                env=self.env, ready_timeout_s=self.ready_timeout_s,
            )))
        self.apiserver_children = children
        self.api_urls = [c.url() for c in children]
        self.api_url = self.api_urls[0]

    # ------------------------------------------------------------- accessors
    def scheduler_diag_urls(self) -> list[str]:
        """Each live replica's diagnostics base URL (its banner's
        ``url``) — the /metrics the mp runner scrapes for conflict
        evidence. Restarted replicas re-banner, so this is always the
        CURRENT address."""
        return [c.url() for c in self.schedulers if c.url()]

    def n_processes(self) -> int:
        return len(self.supervisor.children)

    # ------------------------------------------------------------- lifecycle
    def kill_replica(self, index: int) -> str:
        """SIGKILL scheduler replica ``index`` (the crash the restart
        policy answers). Returns the child name for event matching."""
        name = self.schedulers[index].name
        self.supervisor.kill(name)
        return name

    def join(self, verify=None) -> None:
        self.supervisor.join(verify=verify)

    def shutdown(self) -> None:
        if self.supervisor is not None:
            self.supervisor.shutdown()

    def __enter__(self) -> "Cluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()
