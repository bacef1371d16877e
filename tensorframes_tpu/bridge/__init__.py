"""External-process front-end bridge (the L2 interop layer).

The reference's L2 is a Py4J socket protocol: the Python front-end drives a
JVM engine through ``PythonOpBuilder`` accessors
(``/root/reference/src/main/scala/org/tensorframes/impl/PythonInterface.scala:46-170``),
shipping programs as serialized GraphDef bytes (via temp files,
``core.py:38-49``).  Here the roles invert — the engine IS Python/JAX — but
the seam survives for the same reason: an external front-end (a Spark
driver, a JVM service, another language) needs a wire protocol to hand
frames and tensor programs to the TPU engine.

* ``serve`` / ``BridgeServer`` — localhost TCP server executing the verb
  protocol against in-process TensorFrames (frames live server-side in a
  registry; only programs, schemas, and requested results cross the wire).
* ``BridgeClient`` — the reference-shaped client: ``create_frame``,
  ``analyze``, builder-style verb calls taking **GraphDef bytes** (the same
  transport the reference uses), ``collect``.

Transport: newline-delimited JSON with base64 tensors — deliberately
dependency-free and implementable from any language in an afternoon, like
the Py4J text protocol it replaces.

Round 11 makes the seam serving-grade: per-request deadlines cancelled
cooperatively at block boundaries, bounded admission with ``ServerBusy``
shedding, token-addressed sessions with idempotent retry after dropped
replies, graceful drain, and an ungated ``health`` RPC (see
``docs/RESILIENCE.md``).

Round 16 adds the multi-tenant THROUGHPUT layer (``docs/SERVING.md``):
request coalescing into bucket-canonical micro-batches over a warm
program pool (``Coalescer`` / ``WarmPool``) and SLO-aware fair-share
admission (``SloScheduler``); continuous decode batching is the paged
``DecodeScheduler`` (``coalescer.py``, round 22).

Round 21 scales the seam OUT: ``fleet`` runs N replicas behind a
rendezvous-hashing ``FleetRouter`` (health-polled, flap-quarantining),
``BridgeClient`` grows router-driven failover (``Draining`` /
connection death / ``SessionLost`` reroute to a healthy peer; durable
jobs migrate via the round-20 journal), and ``BridgeFleet`` provides
the kill/drain/restart/rolling-restart levers plus the shared
compile-cache topology that makes a rejoining replica warm
(``docs/SERVING.md`` fleet section, ``docs/RESILIENCE.md``).
"""

from .client import (
    BridgeClient,
    BridgeError,
    Cancelled,
    DeadlineExceeded,
    Draining,
    RemoteFrame,
    ServerBusy,
    SessionLost,
    busy_backoff_s,
)
from .fleet import BridgeFleet, FleetClient, FleetRouter
from .coalescer import (
    Coalescer,
    SloScheduler,
    WarmPool,
    WarmSpec,
)
from .server import BridgeServer, serve

__all__ = [
    "BridgeClient",
    "BridgeError",
    "BridgeFleet",
    "BridgeServer",
    "Cancelled",
    "Coalescer",
    "DeadlineExceeded",
    "Draining",
    "FleetClient",
    "FleetRouter",
    "RemoteFrame",
    "ServerBusy",
    "SessionLost",
    "SloScheduler",
    "WarmPool",
    "WarmSpec",
    "busy_backoff_s",
    "serve",
]
