"""Multi-device execution: mesh utilities + the distributed executor.

Replaces the reference's distribution substrate (Apache Spark, SURVEY.md §2.7)
with XLA collectives over a ``jax.sharding.Mesh``:

* P1 data parallelism over partitions -> blocks sharded over the mesh's data
  axis;
* P4 driver-coordinated pairwise reduce -> on-device tree / ``psum`` over ICI;
* P5 shuffle-grouped aggregation -> device-side keyed reduction;
* P6 program broadcast -> the jit cache (PJRT ships the executable).

Between the single-device ``Executor`` and the GSPMD ``MeshExecutor`` sits
the **device-pool scheduler** (``ops/device_pool.py``, re-exported here):
the default ``Executor`` spreads a host-fresh frame's independent blocks
across all local devices — per-device prefetch lanes, async dispatch,
overlapped readback — which is the paper's per-partition data parallelism
at single-host scale, with no mesh and no collectives.  ``TFS_DEVICE_POOL``
sizes it; ``pool_devices()``/``pool_enabled()`` report the resolved pool.
"""

import sys

# This package's Pallas kernels (``flash``, ``paged_attention``, ``retention``,
# ``ssm``) are TPU kernels.  ``jax.experimental.pallas`` imports its GPU interpreter beside
# the TPU backend — an LLVM dialect and Mosaic GPU, 0.7 s of the 1.2 s the
# import takes on a v5e host, in the set-up of every process that serves
# decode (PERF.md §6, PR 30) — and is written to do without it (``except
# ImportError`` in ``pallas_call``).  A None entry makes that import raise;
# where Pallas is already loaded this does nothing, and a jax that moves the
# module only loses the saving.
sys.modules.setdefault("jax._src.pallas.mosaic_gpu.interpret", None)

from ..ops.device_pool import enabled as pool_enabled, pool_devices  # noqa: E402
from .dist import MeshExecutor  # noqa: E402
from .mesh import data_mesh, device_count, training_mesh  # noqa: E402
from .multihost import (  # noqa: E402
    frame_from_process_local,
    initialize,
    process_count,
    process_index,
)

__all__ = [
    "MeshExecutor",
    "data_mesh",
    "device_count",
    "training_mesh",
    "initialize",
    "frame_from_process_local",
    "process_count",
    "process_index",
    "pool_devices",
    "pool_enabled",
]
