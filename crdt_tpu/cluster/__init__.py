"""Cluster runtime — the anti-entropy layer above :mod:`crdt_tpu.sync`.

The sync package reconciles ONE pair of replicas over an assumed-good
byte stream; this package runs a FLEET: hardened transports (deadlines,
bounded backoff-with-jitter retries, a finite retry budget — the ARQ in
:mod:`~crdt_tpu.cluster.transport`), a peer registry with
alive/suspect/dead health driven by consecutive failures
(:mod:`~crdt_tpu.cluster.membership`), a gossip scheduler that each
round syncs the stalest peers first off the convergence gauges
(:mod:`~crdt_tpu.cluster.gossip`), and a deterministic, seeded fault
injector to prove all of it converges under loss and flapping links
(:mod:`~crdt_tpu.cluster.faults`).

Everything observable feeds ``crdt_tpu_cluster_*`` metrics and the
flight recorder; everything that fails speaks the
:class:`~crdt_tpu.error.TransportError` taxonomy.  docs/GUIDE.md "Cluster
runtime" documents the defaults and the knobs.
"""

from .faults import (  # noqa: F401
    CrashPlan,
    CrashState,
    FaultPlan,
    FaultyTransport,
    FlappingDialer,
    InjectedCrash,
    LatencyTransport,
    TornWriter,
    arm_crashes,
    crash_point,
    disarm_crashes,
    latency_pair,
)
from .gossip import (  # noqa: F401
    ClusterNode,
    GossipScheduler,
    RoundReport,
    hello_accept,
    hello_dial,
)
from .membership import (  # noqa: F401
    ALIVE,
    DEAD,
    SUSPECT,
    Membership,
    PeerInfo,
)
from .transport import (  # noqa: F401
    CallableTransport,
    QueuePairTransport,
    ResilientTransport,
    RetryPolicy,
    TcpTransport,
    Transport,
    queue_pair,
)

__all__ = [
    "ALIVE",
    "DEAD",
    "SUSPECT",
    "CallableTransport",
    "ClusterNode",
    "CrashPlan",
    "CrashState",
    "FaultPlan",
    "FaultyTransport",
    "FlappingDialer",
    "InjectedCrash",
    "TornWriter",
    "arm_crashes",
    "crash_point",
    "disarm_crashes",
    "GossipScheduler",
    "LatencyTransport",
    "Membership",
    "PeerInfo",
    "QueuePairTransport",
    "ResilientTransport",
    "RetryPolicy",
    "RoundReport",
    "TcpTransport",
    "Transport",
    "hello_accept",
    "hello_dial",
    "latency_pair",
    "queue_pair",
]
