"""Fleet replica entrypoint (round 21): one bridge server, one OS
process.

``python -m tensorframes_tpu.bridge.replica --host H --port P --name N``
serves a :class:`~tensorframes_tpu.bridge.server.BridgeServer` on
(H, P) until SIGTERM, which triggers the round-11 graceful drain
(reject new admissions, finish in-flight requests, cooperatively cancel
stragglers) and exits 0 — the "drain" half of a rolling restart.
SIGKILL (the ``replica_kill`` fault, or an impatient operator) skips
all of that, which is the point: the fleet's journal-backed migration
is what makes that death survivable.

Everything else — journal dir, fleet registry, fault specs — arrives
via the environment the spawner
(:class:`~tensorframes_tpu.bridge.fleet.BridgeFleet`) builds, so this
module stays a thin arg-parse around :func:`serve`.  The compile cache
is the entry points' one (``compile_cache.configure_entry_point``):
``JAX_COMPILATION_CACHE_DIR``, else ``TFS_COMPILE_CACHE``, else
``<checkout>/.cache/jax`` — shared by every replica either way.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tensorframes_tpu.bridge.replica",
        description="run one bridge fleet replica (SIGTERM = drain)",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--name", default="")
    args = p.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    log = logging.getLogger("tensorframes_tpu.bridge.replica")

    if args.name:
        # the server reads its replica name from the env; pin it here
        # too so a hand-launched replica (no fleet spawner) still gets
        # a stable identity from --name
        from ..envutil import env_set_default
        from .fleet import ENV_FLEET_REPLICA

        env_set_default(ENV_FLEET_REPLICA, args.name)

    from .. import compile_cache
    from .server import serve

    compile_cache.configure_entry_point()
    server = serve(host=args.host, port=args.port, background=True)
    log.info(
        "replica %s pid=%d serving on %s:%d",
        args.name or "?",
        os.getpid(),
        server.address[0],
        server.address[1],
    )

    done = threading.Event()

    def _on_term(signum, frame):  # noqa: ARG001 — signal signature
        log.info("replica %s: SIGTERM — draining", args.name or "?")
        # drain off the signal handler's thread: close() blocks on
        # in-flight requests, and a handler must return promptly
        threading.Thread(
            target=lambda: (server.close(), done.set()), daemon=True
        ).start()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    done.wait()
    log.info("replica %s: drained, exiting", args.name or "?")
    return 0


if __name__ == "__main__":
    sys.exit(main())
