"""The execution engine: the six verbs, single-device XLA edition.

Re-design of the reference engine ``DebugRowOps``
(``/root/reference/src/main/scala/org/tensorframes/impl/DebugRowOps.scala:281-970``).
The mapping, per SURVEY.md §2.7:

* per-partition TF sessions (P1) -> one jit-compiled XLA executable reused for
  every block with the same signature (jax's jit cache *is* the program
  broadcast, P6);
* partition blocks (P2) -> contiguous columnar arrays, a single ``device_put``
  each instead of per-row ``TensorConverter`` appends;
* ``map_rows`` -> ``vmap`` of the cell-level program over the block's lead
  axis (instead of one session.run per row, ``DebugRowOps.scala:819-857``);
* ``reduce_rows``'s sequential pairwise fold (``performReducePairwise``,
  ``DebugRowOps.scala:930-969``) -> a balanced binary tree of ``vmap``-ed
  pairwise applications, traced with static sizes (deterministic; a
  ``mode="sequential"`` ``lax.scan`` fold reproduces the reference's exact
  left-fold order for non-associative programs);
* ``reduce_blocks``'s two phases (``DebugRowOps.scala:503-526``) -> per-block
  reduce, then ONE re-application of the same block program to the stacked
  partials (the contract already requires the program to reduce any-size
  blocks, so no pairwise driver loop is needed);
* ``aggregate``'s shuffle + buffered UDAF (``DebugRowOps.scala:547-695``) ->
  host group-index build + size-bucketed ``vmap`` of the block program over
  all groups of equal cardinality (no buffer-size-10 compaction artifact).

The ``Executor`` here is single-PROGRAM; on a multi-chip host the
device-pool scheduler (``ops/device_pool.py``, ``TFS_DEVICE_POOL``)
spreads a host-fresh frame's independent blocks across all local devices
— the reference's per-partition data parallelism (SURVEY P1/P4) at
single-host scale, bit-identical to the serial path.
``tensorframes_tpu.parallel`` provides the mesh/``shard_map`` executor
with collective cross-shard reduction for the GSPMD form.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import cancellation, dtypes, envutil, faults, observability
from ..frame import Column, TensorFrame
from ..program import Program, device_of
from ..schema import ColumnInfo, Schema
from ..shape import Shape, ShapeError, UNKNOWN
from . import (
    block_loop,
    bucketing,
    fault_tolerance,
    prefetch,
    segment_compile,
    validation,
)
from ..analysis import rowdep as analysis
from .validation import ValidationError


def _check_shape_hints(
    program: Program, outs: Mapping[str, Any], verb: str, cell_level: bool
) -> None:
    """Check real outputs against the program's shape hints (the run-time
    half of the ``ShapeDescription`` contract: a hint the engine cannot
    satisfy is an error, not a silent discard — VERDICT r1 weak #6).

    ``cell_level``: map_rows hints describe per-row cell shapes; block-verb
    hints describe whole block shapes (reference ``core.py:52-72``)."""
    hints = program.shape_hints
    if not hints:
        return
    for name, hint in hints.items():
        if name not in outs:
            raise ValidationError(
                f"{verb}: shape hint given for {name!r}, which is not a "
                f"program output; outputs are {sorted(outs)}."
            )
        actual = Shape(outs[name].shape)
        if cell_level:
            actual = actual.tail() if actual.rank else actual
        try:
            actual.check_more_precise_than(hint, f"{verb} output {name!r}")
        except ShapeError as e:
            raise ValidationError(
                f"{verb}: output {name!r} has shape {actual}, which "
                f"contradicts the declared shape hint {hint}."
            ) from e


def _with_prelude(program: Program, host_stage):
    """Merge the program's ``host_prelude`` (e.g. the GraphDef importer's
    in-graph Decode* stages) under any caller-supplied ``host_stage`` —
    an explicit stage wins per input."""
    prelude = getattr(program, "host_prelude", None)
    if not prelude:
        return host_stage
    merged = dict(prelude)
    merged.update(host_stage or {})
    return merged


def _np(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


class _MapTimes:
    """One map verb's head and tail, as spans and as time counters:
    ``engine.head`` runs from the verb's entry to the start of its block
    loop (validation, bucket plan, pool and session set-up),
    ``engine.tail`` from the last block enqueued to the return (the
    pool's drain, output assembly).  Bumped once, with the verb's own
    time, at the return; a verb that raises counts nothing."""

    __slots__ = ("_t0", "_head", "_head_ns", "_tail")

    def __init__(self):
        self._t0 = time.perf_counter_ns()
        self._head = observability.span("engine.head", "verbs")
        self._head_ns = 0
        self._tail = None

    def first_block(self) -> None:
        if self._head is not None:
            self._head_ns = self._head.end()
            self._head = None

    def last_block(self) -> None:
        self._tail = observability.span("engine.tail", "verbs")

    def done(self) -> None:
        self.first_block()  # an empty frame has no block loop
        tail_ns = self._tail.end() if self._tail is not None else 0
        observability.note_map_verb(
            time.perf_counter_ns() - self._t0, self._head_ns, tail_ns
        )


class _MapWork(block_loop.Work):
    """The map verbs' blocks, as the one block loop sees them
    (``ops/block_loop.py``): ``_device_inputs`` with the bucket target
    stages a block, the memoised entries (``_rows_run`` / ``_block_run``
    / ``_run_block_program``) run it, and the bucket padding is sliced
    back off.  Blocks whose every input buffer was freshly staged run
    through a donating executable, so steady-state HBM holds at most the
    prefetch window of input blocks; blocks with device-resident inputs
    (cached frames, chained verbs) keep the plain non-donating entries —
    donating a shared column buffer would corrupt the frame
    (prefetch.py's safety contract).  Streamed blocks (``_stream_plan``)
    prefetch + donate at chunk granularity instead."""

    def __init__(
        self, ex, program, frame, infos, host_stage, rows_level, trim,
        plans, pads, donate, fresh,
    ):
        self.ex = ex
        self.program = program
        self.frame = frame
        self.infos = infos
        self.host_stage = host_stage
        self.rows_level = rows_level
        self.trim = trim
        self.plans = plans
        self.pads = pads
        self.donate = donate
        self.sizes = frame.block_sizes
        self.name = "map_rows" if rows_level else "map_blocks"
        self.reads = frozenset(
            program.column_for_input(n)
            for n in program.input_names
            if not (host_stage and n in host_stage)
        )
        self.streams = frozenset(
            bi for bi, p in enumerate(plans) if p is not None
        )
        self.in_order = bool(host_stage)
        # only spin up a staging thread when some block will actually
        # stage on it; otherwise (device-resident frame, or every block
        # streamed at chunk level) keep the plain consumer loop
        self.ahead = fresh and len(self.streams) < frame.num_blocks

    def stage(self, bi, block, device):
        return self.ex._device_inputs(
            self.program, block, self.infos, self.host_stage,
            pad_to=self.pads[bi], device=device,
        )

    def run(self, bi, inputs):
        if self.rows_level:
            outs = self.ex._rows_run(self.program, self.donate)(inputs)
        elif self.donate:
            outs = self.ex._block_run(self.program, True)(inputs)
        else:
            outs = self.ex._run_block_program(self.program, inputs)
        del inputs
        if self.pads[bi] is not None:
            # bucket-padded execution: slice the pad rows back off
            # (row-independence guarantees real rows' values are
            # bit-identical to the exact-shape path)
            n_rows = self.sizes[bi]
            outs = {k: v[:n_rows] for k, v in outs.items()}
        return outs

    def run_streamed(self, bi, device, session, resolver, stats):
        return self.ex._run_block_streamed(
            self.program, self.frame.block(bi), self.infos, self.plans[bi],
            rows_level=self.rows_level, pf_stats=stats, device=device,
            bi=bi, session=session, device_resolver=resolver,
        )

    def check(self, bi, outs):
        self.ex._check_block_outputs(
            self.program, outs, self.sizes[bi], self.rows_level, self.trim
        )

    def params_resident(self, outs):
        # a block's outputs lie where it ran, whatever the placement
        # (and wherever a quarantine redirect or a retry landed it)
        return self.program.params_resident(
            device_of(next(iter(outs.values())))
        )

    # -- OOM degradation (round 9, ops/fault_tolerance.py) ------------------
    # Re-staging under the retry session re-runs any ``host_stage`` fn
    # for the retried block — the same semantics as Spark's lineage
    # replay, which re-executes the whole partition pipeline on task
    # retry and therefore requires deterministic tasks.  The retry
    # contract requires the same of stage fns: deterministic per (block,
    # cells), like the decode fns that motivate ``host_stage``.  A stage
    # fn whose output depends on invocation order cannot participate in
    # block retry (run it with ``TFS_BLOCK_RETRIES=0``, where every
    # error surfaces unretried).

    def oom_split(self, bi, session, devices, pool, di):
        """The OOM-degradation policy for one map-verb block: split the
        block in half and re-dispatch (recursively, floor
        ``TFS_MIN_SPLIT_ROWS``) when that is provably semantics-safe —
        ``map_rows`` is row-independent by construction, ``map_blocks``
        must pass the jaxpr proof at EVERY size the split can reach.
        Trimmed maps (program-defined output row count), host-staged
        blocks (one-unit staging contract), and cross-row programs
        surface a :class:`fault_tolerance.BlockExecutionError` naming
        the block and row range instead."""
        n_rows = self.sizes[bi]

        def refuse(exc: BaseException, why: str):
            raise fault_tolerance.BlockExecutionError(
                f"{self.name}: block {bi} rows [0, {n_rows}) exhausted "
                f"device memory and cannot degrade by splitting: {why}"
            ) from exc

        def split(exc: BaseException) -> Dict[str, Any]:
            floor = fault_tolerance.min_split_rows()
            if self.trim:
                refuse(exc, "trimmed maps define their own output row "
                            "count, so half-block outputs cannot be "
                            "reassembled")
            if self.host_stage:
                refuse(exc, "host-staged blocks stage as one unit")
            if n_rows < 2 * floor:
                refuse(
                    exc,
                    f"the block is already at the split floor "
                    f"(TFS_MIN_SPLIT_ROWS={floor})",
                )
            if not self.rows_level:
                # every size the recursive split can reach, proven
                # row-independent in one shot (memoized on the program)
                sizes = set()
                stack = [(0, n_rows)]
                while stack:
                    lo, hi = stack.pop()
                    sizes.add(hi - lo)
                    if hi - lo >= 2 * floor:
                        mid = (lo + hi) // 2
                        stack += [(lo, mid), (mid, hi)]
                specs = analysis.input_specs_for(self.program, self.infos)
                if specs is None or not analysis.rows_independent(
                    self.program, specs, sorted(sizes)
                ):
                    refuse(
                        exc,
                        "the program is not provably row-independent "
                        "(cross-row outputs cannot be recomputed from "
                        "half blocks)",
                    )
            dev_i = (
                pool.effective_device(di)
                if pool is not None
                else 0  # serial dispatch = device 0
            )
            dev = devices[dev_i] if devices is not None else None
            return self._split_halves(session, bi, 0, n_rows, dev, dev_i)

        return split

    def _split_halves(
        self, session, bi: int, lo: int, hi: int, dev, dev_i: Optional[int]
    ) -> Dict[str, Any]:
        mid = (lo + hi) // 2
        left = self._split_range(session, bi, lo, mid, dev, dev_i)
        right = self._split_range(session, bi, mid, hi, dev, dev_i)
        session.note_split(bi)
        return {k: jnp.concatenate([left[k], right[k]]) for k in left}

    def _split_range(
        self, session, bi: int, lo: int, hi: int, dev, dev_i: Optional[int]
    ) -> Dict[str, Any]:
        """Dispatch rows ``[lo, hi)`` of block ``bi``, splitting again on
        a further OOM until ``TFS_MIN_SPLIT_ROWS``.  Sub-dispatches use
        the plain non-donating entries (fresh small buffers; donation
        would fork another executable per split size for no HBM win) and
        their injected-fault site is ``"split"`` so attempt-selected
        specs never re-fire on recovery work."""
        floor = fault_tolerance.min_split_rows()
        try:
            faults.maybe_inject(bi, 0, dev_i, hi - lo, site="split")
            block = self.frame.block(bi)
            sub = {k: v[lo:hi] for k, v in block.items()}
            inputs = self.ex._device_inputs(
                self.program, sub, self.infos, None, device=dev
            )
            if self.rows_level:
                return self.program.vmapped()(inputs)
            return self.ex._run_block_program(self.program, inputs)
        except BaseException as exc:  # noqa: BLE001 - OOM-only recovery
            if not faults.is_oom(exc):
                raise
            if hi - lo < 2 * floor:
                raise fault_tolerance.BlockExecutionError(
                    f"block {bi} rows [{lo}, {hi}) exhausted device "
                    f"memory at the split floor (TFS_MIN_SPLIT_ROWS="
                    f"{floor}); this row range does not fit on the device"
                ) from exc
            return self._split_halves(session, bi, lo, hi, dev, dev_i)


class _ReduceWork(block_loop.Work):
    """The reduce verbs' per-block partials: each base column of the
    block cast and moved to its device, folded there by ``run`` — the
    device-granularity analog of the reference's per-partition reduce
    (SURVEY P1/P4).  Reduce partials are cross-row by definition: no OOM
    split — an OOM surfaces with the block's row range."""

    name = "reduce"
    span = "engine.reduce_block"
    to_host = False

    def __init__(self, ex, run, bases, sts, cols):
        self.ex = ex
        self._run = run
        self.bases = bases
        self.sts = sts
        self.cols = cols
        self.reads = frozenset(cols.values())

    def stage(self, bi, block, device):
        return {
            b: self.ex._device_value(
                block[self.cols[b]], self.sts[b], device=device
            )
            for b in self.bases
        }

    def run(self, bi, inputs):
        return self._run(inputs)


class GroupedFrame:
    """Result of ``group_by`` — the ``RelationalGroupedDataset`` analog."""

    def __init__(self, frame: TensorFrame, keys: Sequence[str]):
        if not keys:
            raise ValidationError("group_by needs at least one key column")
        for k in keys:
            ci = frame.schema[k]
            if ci.cell_shape.rank != 0:
                raise ValidationError(
                    f"group_by: key column {k!r} must be scalar, has cell "
                    f"shape {ci.cell_shape}"
                )
        self.frame = frame
        self.keys = list(keys)


def group_by(frame: TensorFrame, *keys: str) -> GroupedFrame:
    if getattr(frame, "_tfs_lazy", False):
        # LazyFrame: materialise the plan (aggregate's group structure
        # is data-dependent), counting the grouping as one consumer
        return frame.group_by(*keys)
    return GroupedFrame(frame, keys)


class Executor:
    """Single-device verb executor.

    Data-plane design (SURVEY.md §7 hard part 3 — the throughput term the
    reference lost to per-row ``TensorConverter`` appends and per-partition
    session syncs): every verb *dispatches* all blocks without synchronising —
    ``device_put`` and jitted execution are asynchronous, so the host->HBM
    transfer of block N+1 overlaps the compute of block N — and outputs stay
    on device (``jax.Array`` columns).  The only host syncs are the user's own
    materialisation calls (``collect``/``to_arrays``/``np.asarray``) and the
    single-cell results of the reduce verbs.

    Exception, by design: when the device POOL engages (``TFS_DEVICE_POOL``,
    host-fresh multi-block frame, >=2 local devices) the map verbs return
    host-assembled columns — per-block D2H starts as each block completes
    (overlapped with later blocks' compute) and the verb syncs on the last
    block.  See ``ops/device_pool.py`` for the scope rules.
    """

    # monoid aggregates may run as one device segment reduction; the mesh
    # executor shards the same path over its data axis via _place_rows
    supports_segment_aggregate = True

    # host-fresh multi-block frames may dispatch blocks across ALL local
    # devices (ops/device_pool.py, TFS_DEVICE_POOL); the mesh executor
    # opts out — its GSPMD sharding is its own multi-device story
    supports_device_pool = True

    def _place_rows(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Device placement for a row-axis array in the segment-aggregate
        path.  The mesh executor overrides this to shard the rows over the
        data axis, turning the device sort + segment reduction into a
        GSPMD-distributed one (SURVEY P5 at mesh scale)."""
        return jnp.asarray(arr)

    def _segment_pad_rows(self, n: int) -> int:
        """Rows of identity padding the segment-aggregate path should
        append for a row count of ``n`` — 0 on a single device; the mesh
        executor pads to a data-axis multiple so uneven frames still
        shard over the whole mesh (bare-monoid plans only; see
        ``_aggregate_segment``)."""
        return 0

    # ---------------------------------------------------------------- map --

    def _device_value(self, value: Any, st, device=None) -> jnp.ndarray:
        """One block/column of data -> device array in its compute dtype.

        Device-resident values (chained verb outputs) are used in place —
        at most a device-side cast; host values are cast on host then moved
        with an async ``device_put`` (the single-copy replacement for
        ``datatypes.scala:93-127``).  ``device``: explicit placement for
        the device-pool scheduler's per-device staging lanes (None keeps
        jax's default device)."""
        if isinstance(value, jax.Array):
            if value.dtype != st.np_dtype:
                value = value.astype(st.np_dtype)
            if device is not None:
                value = jax.device_put(value, device)
            return value
        arr = np.asarray(value)
        if arr.dtype != st.np_dtype:
            arr = arr.astype(st.np_dtype)
        observability.note_h2d_bytes(arr.nbytes)
        return jax.device_put(arr, device)

    def _staged_value(self, stage_fn, value, input_name: str) -> np.ndarray:
        """Run one host_stage fn over a block's cells and shape-check the
        result — the host half of the reference's binary-feed contract
        (``read_image.py:164-167`` feeds encoded bytes to an in-graph
        decoder; XLA cannot host strings, so the decode runs here)."""
        n_rows = len(value)
        if isinstance(value, np.ndarray) and value.dtype == object:
            value = list(value)
        out = np.asarray(stage_fn(value))
        if out.ndim == 0 or out.shape[0] != n_rows:
            raise ValidationError(
                f"host_stage for input {input_name!r} returned shape "
                f"{out.shape}; expected lead dimension {n_rows} (one "
                f"preprocessed cell per input row)."
            )
        if out.dtype == object:
            raise ValidationError(
                f"host_stage for input {input_name!r} must return a uniform "
                f"numeric array, got dtype=object (ragged cells)."
            )
        return out

    def _device_inputs(
        self,
        program: Program,
        block: Mapping[str, Any],
        infos: Mapping[str, ColumnInfo],
        host_stage: Optional[Mapping[str, Any]] = None,
        pad_to: Optional[int] = None,
        device=None,
    ) -> Dict[str, jnp.ndarray]:
        """``pad_to``: bucket target for the block's row axis (shape-
        canonical execution).  Host blocks pad in numpy *before* the
        ``device_put``, so the staged transfer already carries the padded
        signature (prefetch worker included); device-resident blocks pad
        with a device-side concat on the consumer thread.  Callers slice
        the outputs back to the true row count.  ``device``: explicit
        target for the device-pool staging lanes."""
        inputs = {}
        for n in program.input_names:
            value = block[program.column_for_input(n)]
            if host_stage and n in host_stage:
                value = self._staged_value(host_stage[n], value, n)
                st = dtypes.coerce(dtypes.from_numpy(value.dtype))
            else:
                st = dtypes.coerce(infos[n].scalar_type)
            if pad_to is not None and not isinstance(value, jax.Array):
                value = bucketing.pad_rows(np.asarray(value), pad_to)
            value = self._device_value(value, st, device=device)
            if pad_to is not None and isinstance(value, jax.Array):
                value = bucketing.pad_rows(value, pad_to)
            inputs[n] = value
        return inputs

    def _run_block_program(self, program: Program, inputs) -> Dict[str, Any]:
        return program.jitted()(inputs)

    # -- donated entries (prefetch path) ------------------------------------
    # A donating executable invalidates its input buffers, letting XLA
    # reuse them for outputs: with the Prefetcher's bounded window the
    # steady-state HBM footprint of uncached ingestion is <= depth input
    # blocks regardless of frame size.  ONLY freshly staged buffers may
    # flow through these (prefetch.py's no-use-after-donate contract);
    # device-resident (cached/chained) columns keep the plain entries.

    def _block_run(self, program: Program, donate: bool):
        if not donate:
            return program.jitted()
        return program.cached_jit(
            ("map_blocks", "donated"),
            lambda: lambda ins, ps: program.call(ins, ps),
            donate_argnums=(0,),
        )

    def _rows_run(self, program: Program, donate: bool):
        if not donate:
            return program.vmapped()
        return program.cached_jit(
            ("map_rows", "donated"),
            lambda: lambda ins, ps: jax.vmap(
                lambda i: program.call(i, ps), in_axes=(0,)
            )(ins),
            donate_argnums=(0,),
        )

    # h2d streaming granularity for uncached blocks (VERDICT r4 weak #3):
    # a block whose host->device transfer exceeds ~2 chunks is split into
    # row slices, each device_put + dispatched separately, so chunk k+1's
    # transfer overlaps chunk k's compute INSIDE the block instead of the
    # whole block's bytes landing before any compute starts.  Applied only
    # to row-independent programs per the shared gate (analysis.
    # rows_independent: static classification first, exact-size probe on
    # UNKNOWN) — cross-row programs need the whole block.
    # Tunable: TFS_STREAM_CHUNK_BYTES (0 disables).
    stream_chunk_bytes = envutil.env_int(
        "TFS_STREAM_CHUNK_BYTES", 64 * 1024 * 1024
    )

    def _stream_plan(
        self,
        program: Program,
        block,
        infos,
        host_stage,
        check_independence: bool = True,
    ) -> Optional[int]:
        """Rows per chunk for streamed ingestion of this block, or None
        to take the unstreamed path (device-resident inputs, small
        blocks, host-staged inputs, cross-row programs)."""
        chunk = self.stream_chunk_bytes
        if not chunk or host_stage:
            return None
        total = 0
        n_rows = None
        for name in program.input_names:
            value = block[program.column_for_input(name)]
            if isinstance(value, jax.Array):
                return None  # already on device: nothing to stream
            arr = np.asarray(value)
            if arr.dtype == object:
                return None
            st = dtypes.coerce(infos[name].scalar_type)
            total += arr.size * np.dtype(st.np_dtype).itemsize
            n_rows = arr.shape[0] if arr.ndim else None
            if n_rows is None:
                return None
        if n_rows is None or total < 2 * chunk:
            return None
        n_chunks = -(-total // chunk)
        per = -(-n_rows // n_chunks)
        if per >= n_rows:
            return None
        if check_independence:
            # statically classified once per program (analysis.rowdep);
            # unclassifiable programs probe at the EXACT executed sizes
            # (semantic block size, chunk size, tail size) — sound
            # against python control flow branching at any threshold
            specs = analysis.input_specs_for(program, infos)
            tail = n_rows % per or per
            if specs is None or not analysis.rows_independent(
                program, specs, (n_rows, per, tail)
            ):
                return None
        return per

    def _run_block_streamed(
        self,
        program: Program,
        block,
        infos,
        per: int,
        rows_level: bool = False,
        pf_stats: Optional[Dict[str, Any]] = None,
        device=None,
        bi: int = 0,
        session=None,
        device_resolver=None,
    ) -> Dict[str, Any]:
        """Chunked h2d + dispatch: equal row slices (last may be short, so
        at most two executables trace), outputs concatenated on device.
        ``device``: chunk staging target under the device-pool scheduler
        (the whole block's chunks stream to the block's assigned device).

        The chunks run through a :class:`prefetch.Prefetcher`: chunk k+1's
        cast + ``device_put`` happen on the staging thread while chunk k's
        compute dispatches, and each chunk's staged buffers are donated to
        the executable (fresh per chunk by construction), so HBM holds at
        most the prefetch window of input chunks.  ``rows_level`` picks the
        vmapped cell entry (map_rows); ``pf_stats`` (a caller-LOCAL dict,
        never a live Prefetcher's stats — the outer staging thread writes
        those concurrently) accumulates the chunk prefetcher's totals for
        the caller's span record.  ``device_resolver``: zero-arg callable
        returning the CURRENT ``(device index, device)`` target under the
        pool — re-resolved per retry attempt so chunk re-dispatches
        follow a quarantine redirect instead of hammering a drained
        device (serial callers leave it None: device 0, ``device``)."""
        names = program.input_names
        arrays = {}
        n_rows = 0
        for nm in names:
            arrays[nm] = np.asarray(block[program.column_for_input(nm)])
            n_rows = arrays[nm].shape[0]
        starts = list(range(0, n_rows, per))
        # shape-canonical chunks: pad the short tail chunk up to ``per``
        # so ONE executable serves every chunk (the independence proof
        # already ran at the tail size; map_rows chunks are independent
        # by construction).  The pad rows are sliced off the concat.
        pad_tail = bucketing.enabled() and n_rows % per != 0

        def stage(k, _dev=None):
            sl = slice(starts[k], min(starts[k] + per, n_rows))
            staged = {
                nm: arrays[nm][sl] for nm in names
            }
            if pad_tail and sl.stop - sl.start < per:
                staged = {
                    nm: bucketing.pad_rows(v, per) for nm, v in staged.items()
                }
            return {
                nm: self._device_value(
                    v,
                    dtypes.coerce(infos[nm].scalar_type),
                    device=_dev if _dev is not None else device,
                )
                for nm, v in staged.items()
            }

        donate = prefetch.donate_inputs()
        run = (
            self._rows_run(program, donate)
            if rows_level
            else self._block_run(program, donate)
        )
        pf = prefetch.Prefetcher(stage, len(starts))
        outs: List[Dict[str, Any]] = []
        for k, inputs in enumerate(pf):
            # chunk boundary = cancellation checkpoint (the streamed
            # analog of the block-boundary check): a deadline cuts the
            # streamed dispatch between chunks instead of waiting out
            # the whole block; a no-op contextvar read without a scope
            cancellation.checkpoint()
            if session is None:
                outs.append(run(inputs))
                del inputs
                continue
            # chunk-granular retry: each chunk dispatch is its own
            # attempt unit (fault injection keys on the BLOCK index, so
            # a block-selected spec fires per chunk — deterministic
            # either way).  A retried chunk re-stages on the consumer
            # thread; its fresh buffers stay donation-eligible.  No OOM
            # split here: chunks are already the streaming granularity,
            # so a chunk OOM surfaces with its exact row range.
            lo = starts[k]
            hi = min(starts[k] + per, n_rows)
            holder = {"v": inputs}
            del inputs

            def attempt(a, dev_i, _k=k, _h=holder):
                ins = _h.pop("v", None)
                if a > 0 or ins is None:
                    # re-stage to the CURRENT effective device, so a
                    # retried chunk follows a quarantine redirect
                    dev_now = (
                        device_resolver()[1]
                        if device_resolver is not None
                        else None
                    )
                    ins = stage(_k, dev_now)
                return run(ins)

            outs.append(
                session.run(
                    bi,
                    hi - lo,
                    attempt,
                    device=(
                        (lambda: device_resolver()[0])
                        if device_resolver is not None
                        else 0
                    ),
                    row_range=(lo, hi),
                )
            )
        if pf_stats is not None:
            pf_stats["items"] += pf.stats["items"]
            pf_stats["stage_s"] += pf.stats["stage_s"]
            pf_stats["wait_s"] += pf.stats["wait_s"]
        if (
            session is not None
            and device_resolver is not None
            and session.pool is not None
            and session.pool.quarantined
        ):
            # a mid-block quarantine redirect left chunk outputs on more
            # than one device; co-locate them on the current effective
            # device before the concat (committed arrays on different
            # devices cannot feed one op)
            _, dev_final = device_resolver()
            outs = [
                {k2: jax.device_put(v, dev_final) for k2, v in o.items()}
                for o in outs
            ]
        cat = {k: jnp.concatenate([o[k] for o in outs]) for k in outs[0]}
        if pad_tail:
            cat = {k: v[:n_rows] for k, v in cat.items()}
        return cat

    def _bucket_plan(
        self,
        program: Program,
        frame: TensorFrame,
        infos,
        host_stage,
        rows_level: bool,
        trim: bool,
        stream_plans: Sequence[Optional[int]],
    ) -> List[Optional[int]]:
        """Per-block bucket targets for shape-canonical execution, or None
        per block to run the exact shape.

        ``map_rows`` blocks pad freely — the cell program is vmapped over
        the row axis, so rows are independent by construction.
        ``map_blocks`` padding is gated on the shared row-independence
        gate (``analysis.rows_independent``): the memoized size-generic
        classification answers first, and the exact-size compile probe
        (``segment_compile.cached_rows_independent``) runs on
        ``UNKNOWN`` — together rejecting cross-row programs, block-size
        literals, and size-branching python control flow (for classified
        programs, up to the canonical-probe envelope documented in
        ``analysis/rowdep.py``; ``TFS_ANALYZE_XCHECK=1`` is the fence).
        Refused programs keep exact shapes and their per-size
        executables.  Out of scope, by design: trimmed maps (the output
        row count is program-defined, so sliced-back padding has no
        defined contract), host-staged ``map_blocks`` inputs (the staged
        cell shape is unknown before the stage fn runs, so the proof
        cannot be posed), and blocks already streamed in canonical chunks
        (``stream_plans``)."""
        nb = frame.num_blocks
        none_plan: List[Optional[int]] = [None] * nb
        if trim or not bucketing.enabled():
            return none_plan
        if host_stage and not rows_level:
            return none_plan
        sizes = frame.block_sizes
        targets = [
            bucketing.bucket_for(n)
            if n > 0 and stream_plans[bi] is None
            else None
            for bi, n in enumerate(sizes)
        ]
        targets = [
            t if t is not None and t != sizes[bi] else None
            for bi, t in enumerate(targets)
        ]
        if all(t is None for t in targets):
            return none_plan
        if not rows_level:
            # one structural proof across every (real, padded) size pair
            # this frame will execute
            proof_sizes = sorted(
                {sizes[bi] for bi, t in enumerate(targets) if t is not None}
                | {t for t in targets if t is not None}
            )
            specs = analysis.input_specs_for(program, infos)
            if specs is None or not analysis.rows_independent(
                program, specs, proof_sizes
            ):
                return none_plan
        return targets

    def _frame_fresh(self, frame: TensorFrame) -> bool:
        """The ONE freshness rule behind input donation, shared by the
        dispatch loop and :meth:`warmup` (the warmup executable must
        carry the same donation aliasing the first real dispatch will,
        or the persistent-cache keys diverge).

        Residency is a COLUMN property (one array sliced per block), so
        freshness is decided once per frame, on the consumer thread.  It
        covers EVERY column, not just the program's inputs, because the
        worker's ``frame.block()`` slices all of them — and slicing a
        device column (jax.Array.__getitem__) is a jit entry point,
        which the Prefetcher contract keeps off the worker.  Donation
        eligibility only needs the program's input columns host-side,
        and all-host is a superset of that."""
        return all(
            not frame.column(ci.name).is_device for ci in frame.schema
        )

    def map_blocks(
        self,
        program: Program,
        frame: TensorFrame,
        trim: bool = False,
        host_stage: Optional[Mapping[str, Any]] = None,
    ) -> TensorFrame:
        """``mapBlocks`` (``DebugRowOps.scala:290-393``) /
        ``mapBlocksTrimmed`` (trim=True: output row count may differ, no
        passthrough columns — ``Operations.scala:61-80``).

        All blocks are dispatched asynchronously; no host sync happens here
        (output shapes are static, so row-count validation needs no data).
        ``host_stage``: input name -> host fn(cells) -> [rows, *cell] array,
        run per block before the device program (binary decode, bucketing);
        it executes on ONE prefetch staging thread in block order — under
        the device pool too, where only h2d/compute/readback parallelize —
        so block N+1's host stage AND h2d transfer overlap block N's
        device compute.

        Device pool (``TFS_DEVICE_POOL``, host-fresh multi-block frames on
        a >=2-device host): blocks dispatch across all local devices and
        the verb returns HOST-assembled output columns — each block's D2H
        copy starts as it completes, overlapping later blocks' compute,
        and the verb synchronizes on the last block (the trade the pool
        makes: cross-device parallelism for device residency, so a
        chained verb re-stages its inputs).  The serial single-device
        path keeps the fully async, device-resident contract."""
        host_stage = _with_prelude(program, host_stage)
        with observability.verb_span(
            "map_blocks", frame.num_rows, frame.num_blocks
        ) as span:
            times = _MapTimes()
            infos = validation.check_map_inputs(
                program, frame, "map_blocks", host_staged=host_stage or ()
            )
            span.mark("validate")
            out_blocks = self._map_dispatch(
                program, frame, infos, host_stage, span,
                rows_level=False, trim=trim, times=times,
            )
            span.mark("dispatch")
            out = self._build_map_output(frame, out_blocks, trim)
            times.done()
            return out

    def _map_plan(
        self, program: Program, frame: TensorFrame, infos, host_stage,
        rows_level: bool, trim: bool,
    ):
        """``(placement, stream plans, bucket targets, donate)`` of one
        map verb over ``frame`` — the ONE walk behind both the dispatch
        and :meth:`warmup` (the warmup executables must carry the shapes,
        the devices and the donation aliasing the first real dispatch
        will, or the persistent-cache keys diverge).

        Planned on the caller thread: ``_stream_plan`` and
        ``_bucket_plan`` may trace (row-independence proofs); all jit
        entry points stay off the staging workers.  A sharded-cached
        frame never streams (its bytes are already in HBM) and never
        donates (shards are shared state); bucket targets still apply
        (device-side pad + slice)."""
        nb = frame.num_blocks
        placement = block_loop.place(self, frame, range(nb))
        if placement.cache is not None:
            plans: List[Optional[int]] = [None] * nb
        else:
            plans = [
                self._stream_plan(
                    program, frame.block(bi), infos, host_stage,
                    check_independence=not rows_level,
                )
                for bi in range(nb)
            ]
        # shape-canonical bucket targets (one executable for every block
        # size of this program); streamed blocks canonicalize at chunk
        # granularity inside _run_block_streamed instead
        pads = self._bucket_plan(
            program, frame, infos, host_stage, rows_level, trim, plans
        )
        donate = prefetch.donate_inputs() and placement.fresh
        return placement, plans, pads, donate

    def _map_dispatch(
        self,
        program: Program,
        frame: TensorFrame,
        infos,
        host_stage,
        span,
        rows_level: bool,
        trim: bool,
        times: "_MapTimes",
    ) -> List[Dict[str, Any]]:
        """The two map verbs' dispatch: plan, then hand the blocks to the
        one block loop (``ops/block_loop.py``), which stages them ahead
        (up to ``TFS_PREFETCH_BLOCKS`` on a worker thread, or on
        per-device lanes under the pool), runs them where the placement
        says, and collects the outputs — device-resident under serial
        placement, host-assembled by block index when pooled."""
        if frame.num_rows == 0 and not trim:
            # empty-frame contract: a non-trimmed map of an empty frame is
            # an empty frame with the program's inferred output schema —
            # no trace, no compile, no program execution.  (A TRIMMED map
            # still applies the program to the empty block below: its
            # output row count is program-defined, e.g. a per-block
            # summary row, and inference cannot fabricate those values.)
            return [
                self._empty_map_outputs(
                    program, frame, infos, host_stage, rows_level
                )
            ]
        placement, plans, pads, donate = self._map_plan(
            program, frame, infos, host_stage, rows_level, trim
        )
        work = _MapWork(
            self, program, frame, infos, host_stage, rows_level, trim,
            plans, pads, donate, placement.fresh,
        )
        out_blocks, (items, stage_s, wait_s) = block_loop.run_blocks(
            placement, frame, work, span, times
        )
        if placement.kind != "affinity":  # no lanes: nothing staged ahead
            span.annotate(
                "prefetch",
                {
                    "items": items,
                    "depth": prefetch.prefetch_depth(),
                    "stage_s": round(stage_s, 6),
                    "wait_s": round(wait_s, 6),
                    "overlap_ratio": round(
                        prefetch.overlap_ratio(stage_s, wait_s), 4
                    ),
                    # whether donation actually applied to this verb's
                    # blocks, not just the knob: a device-resident frame
                    # never donates
                    "donate": donate,
                },
            )
        return out_blocks

    def _check_block_outputs(
        self, program: Program, outs, n_rows: int, rows_level: bool,
        trim: bool,
    ) -> None:
        """Per-block output validation of the map verbs: the non-trimmed
        row-count contract, the trimmed agreement contract, and the
        shape-hint check."""
        verb = "map_rows" if rows_level else "map_blocks"
        if rows_level:
            pass  # row programs are per-cell; no block row-count check
        elif not trim:
            for name, v in outs.items():
                if v.ndim == 0 or v.shape[0] != n_rows:
                    raise ValidationError(
                        f"map_blocks: output {name!r} has shape "
                        f"{v.shape} but the input block has {n_rows} "
                        f"rows; a non-trimmed map must preserve the "
                        f"row count (use map_blocks_trimmed to "
                        f"change it)."
                    )
        else:
            counts = {
                v.shape[0] if v.ndim else None for v in outs.values()
            }
            if len(counts) != 1 or None in counts:
                raise ValidationError(
                    f"map_blocks_trimmed: outputs disagree on row "
                    f"count: { {k: v.shape for k, v in outs.items()} }"
                )
        _check_shape_hints(program, outs, verb, cell_level=rows_level)

    def _empty_map_outputs(
        self,
        program: Program,
        frame: TensorFrame,
        infos,
        host_stage,
        rows_level: bool,
    ) -> Dict[str, np.ndarray]:
        """Zero-row output block for the empty-frame map contract, shaped
        by ``Program.analyze`` (host-staged inputs run their stage fn over
        the zero cells so the staged cell shape is authoritative)."""
        specs: Dict[str, Any] = {}
        block0 = frame.block(0)  # the one empty block: real (0, *cell)
        # column slices, so shape-preserving stage fns infer correctly
        for n in program.input_names:
            if host_stage and n in host_stage:
                try:
                    arr = self._staged_value(
                        host_stage[n], block0[program.column_for_input(n)], n
                    )
                except ValidationError:
                    raise
                except Exception as e:
                    raise ValidationError(
                        f"host_stage for input {n!r} failed on an empty "
                        f"frame ({e!r}); a stage fn must accept zero cells "
                        f"for the empty-frame contract to apply."
                    ) from e
                st = dtypes.coerce(dtypes.from_numpy(arr.dtype))
                cell = arr.shape[1:]
            else:
                st = dtypes.coerce(infos[n].scalar_type)
                cell = tuple(infos[n].cell_shape)
            specs[n] = (st, cell if rows_level else (0,) + cell)
        outs: Dict[str, np.ndarray] = {}
        for s in program.analyze(specs):
            if not s.is_output:
                continue
            shape = tuple(s.shape)
            if rows_level:
                shape = (0,) + shape
            elif not shape or shape[0] != 0:
                raise ValidationError(
                    f"map_blocks: output {s.name!r} has inferred shape "
                    f"{shape} for an empty block; a non-trimmed map must "
                    f"preserve the row count (use map_blocks_trimmed to "
                    f"change it)."
                )
            outs[s.name] = np.zeros(shape, dtype=s.scalar_type.np_dtype)
        return outs

    def map_rows(
        self,
        program: Program,
        frame: TensorFrame,
        host_stage: Optional[Mapping[str, Any]] = None,
    ) -> TensorFrame:
        """``mapRows`` (``DebugRowOps.scala:396-477``): the program is written
        at *cell* level and vmapped over the block's rows.  Ragged input
        columns are resolved per row by shape-bucketing (`_map_rows_ragged`)."""
        host_stage = _with_prelude(program, host_stage)
        with observability.verb_span(
            "map_rows", frame.num_rows, frame.num_blocks
        ) as span:
            times = _MapTimes()
            infos = validation.check_map_inputs(
                program,
                frame,
                "map_rows",
                host_staged=host_stage or (),
                allow_ragged=True,
            )
            span.mark("validate")
            ragged = [
                n
                for n in program.input_names
                if not (host_stage and n in host_stage)
                and frame.column(program.column_for_input(n)).is_ragged
            ]
            if ragged:
                times.first_block()  # shape buckets, not the block loop
                out = self._map_rows_ragged(
                    program, frame, infos, host_stage, ragged
                )
                span.mark("dispatch")
                times.done()
                return out
            # row programs are row-independent BY CONSTRUCTION (the cell
            # program is vmapped), so big uncached blocks always stream
            # their h2d in chunks (check_independence=False in the plan)
            out_blocks = self._map_dispatch(
                program, frame, infos, host_stage, span,
                rows_level=True, trim=False, times=times,
            )
            span.mark("dispatch")
            out = self._build_map_output(frame, out_blocks, trim=False)
            times.done()
            return out

    def _run_rows_bucket(
        self, program: Program, arrays: Dict[str, jnp.ndarray]
    ) -> Dict[str, Any]:
        """Run the vmapped cell program over one same-shape row bucket.
        The mesh executor overrides this to pad+shard the bucket (rows are
        independent under vmap, so padding is semantics-safe)."""
        return program.vmapped()(arrays)

    def _ragged_pad_ok(
        self,
        program: Program,
        ragged_name: str,
        rcells: Sequence[np.ndarray],
        uniform: Mapping[str, np.ndarray],
        sizes: Sequence[int],
    ) -> bool:
        """Whether the single ragged input's cells may pad along their
        lead (ragged) axis: jaxpr-proven elementwise along that axis, at
        the exact (real, bucketed) lengths.

        The proof is the shared row-independence gate
        (:func:`analysis.rows_independent` — static classification with
        the exact-size compile probe as fallback) posed on
        the *cell* program with the ragged axis as the lead dim and every
        uniform input bound as a trace param — within one row the uniform
        inputs are constants w.r.t. the cell axis, which is exactly the
        proof's "group" class.  A program that reduces, sorts, or
        position-indexes along the ragged axis (``v.sum()``,
        ``v[::-1]``...) fails and keeps the exact per-shape buckets."""
        rest = {c.shape[1:] for c in rcells}
        if len(rest) != 1:
            return False  # trailing dims ragged too: exact buckets
        st = np.asarray(rcells[0]).dtype
        key = (
            "ragged-pad",
            ragged_name,
            tuple(sorted(sizes)),
            rest.pop(),
            str(st),
            tuple(sorted((u, a.shape[1:], str(a.dtype)) for u, a in uniform.items())),
        )
        cache = program._derived
        if key in cache:
            return cache[key]
        try:
            dummies = {
                u: np.zeros(a.shape[1:], a.dtype) for u, a in uniform.items()
            }
            probe = Program(
                program._fn,
                program.input_names + program.param_names,
                program._declared_fetches,
                None,
                {**program.params, **dummies},
            )
            specs = {
                ragged_name: jax.ShapeDtypeStruct(
                    (2,) + rcells[0].shape[1:], st
                )
            }
            ok = analysis.rows_independent(probe, specs, sizes)
        except analysis.AnalysisXCheckError:
            raise  # the differential fence must fail loudly
        except Exception:
            ok = False
        cache[key] = ok
        return ok

    def _map_rows_ragged(
        self,
        program: Program,
        frame: TensorFrame,
        infos: Mapping[str, ColumnInfo],
        host_stage: Optional[Mapping[str, Any]],
        ragged_names: Sequence[str],
    ) -> TensorFrame:
        """Ragged ``map_rows`` via shape-bucketing (SURVEY.md §7 hard part 1).

        The reference resolves variable per-row lead dims one row at a time
        inside its converter (``TFDataOps.scala:86-103``,
        ``DataOps.inferPhysicalShape`` L105-144); a compiled-program engine
        instead groups rows by their concrete cell shapes and runs ONE
        vmapped execution per distinct shape (bounded recompilation: one
        trace per bucket shape, reused across blocks and calls).

        Round 7 tightens "bounded" from O(distinct shapes) — unbounded if
        the data does not cooperate — to O(log max-dim): when the program
        is provably elementwise along the ragged axis
        (:meth:`_ragged_pad_ok`), rows are grouped by the *geometric
        bucket* of their ragged lead dim (``bucketing.bucket_for``), each
        cell padded up to the bucket by edge repetition, and each output
        row sliced back to its own true length — the pad elements are the
        validity mask's complement, computed and discarded."""
        n = frame.num_rows
        cells: Dict[str, List[np.ndarray]] = {}
        uniform: Dict[str, np.ndarray] = {}
        for in_name in program.input_names:
            col = frame.column(program.column_for_input(in_name))
            if host_stage and in_name in host_stage:
                uniform[in_name] = self._staged_value(
                    host_stage[in_name], col.cells(), in_name
                )
                continue
            st = dtypes.coerce(infos[in_name].scalar_type)
            if in_name in ragged_names:
                cells[in_name] = [
                    np.asarray(c).astype(st.np_dtype, copy=False)
                    for c in col.cells()
                ]
            else:
                uniform[in_name] = np.asarray(col.data).astype(
                    st.np_dtype, copy=False
                )

        # cell-axis bucket padding: single ragged input, pads proven safe
        pad_lengths: Dict[int, int] = {}
        if bucketing.enabled() and len(ragged_names) == 1:
            r = ragged_names[0]
            lengths = sorted({c.shape[0] for c in cells[r] if c.shape[0] > 0})
            targets = {d: bucketing.bucket_for(d) for d in lengths}
            if any(t != d for d, t in targets.items()):
                proof_sizes = sorted(set(lengths) | set(targets.values()))
                if self._ragged_pad_ok(
                    program, r, cells[r], uniform, proof_sizes
                ):
                    pad_lengths = {d: t for d, t in targets.items() if t != d}

        buckets: Dict[Tuple, List[int]] = {}
        for i in range(n):
            key = tuple(
                (pad_lengths.get(cells[r][i].shape[0], cells[r][i].shape[0]),)
                + cells[r][i].shape[1:]
                for r in ragged_names
            )
            buckets.setdefault(key, []).append(i)

        out_cells: Dict[str, List[Any]] = {}
        for key in sorted(buckets):  # deterministic trace order
            idxs = buckets[key]
            arrays: Dict[str, jnp.ndarray] = {}
            for r in ragged_names:
                target = key[0][0] if pad_lengths else None
                arrays[r] = jnp.asarray(
                    np.stack(
                        [
                            bucketing.pad_rows(cells[r][i], target)
                            if target is not None
                            else cells[r][i]
                            for i in idxs
                        ]
                    )
                )
            for u, arr in uniform.items():
                arrays[u] = jnp.asarray(arr[idxs])
            outs = self._run_rows_bucket(program, arrays)
            hosts = {name: np.asarray(v) for name, v in outs.items()}
            if not pad_lengths:
                _check_shape_hints(program, outs, "map_rows", cell_level=True)
                for name, host in hosts.items():
                    if name not in out_cells:
                        out_cells[name] = [None] * n
                    for j, i in enumerate(idxs):
                        out_cells[name][i] = host[j]
                continue
            # padded bucket: every output tracks the ragged axis on dim 0
            # (guaranteed by the _ragged_pad_ok proof) — slice each row's
            # outputs back to its own true length, and hint-check once per
            # distinct true length (shapes differ within the bucket)
            hint_checked: set = set()
            for j, i in enumerate(idxs):
                d = cells[ragged_names[0]][i].shape[0]
                row = {
                    name: host[j][:d] if d < host[j].shape[0] else host[j]
                    for name, host in hosts.items()
                }
                if program.shape_hints and d not in hint_checked:
                    _check_shape_hints(
                        program,
                        {name: cell[None] for name, cell in row.items()},
                        "map_rows",
                        cell_level=True,
                    )
                    hint_checked.add(d)
                for name, cell in row.items():
                    if name not in out_cells:
                        out_cells[name] = [None] * n
                    out_cells[name][i] = cell

        from ..frame import _column_from_cells

        cols = [
            _column_from_cells(name, out_cells[name])
            for name in sorted(out_cells)
        ]
        shadowed = {c.info.name for c in cols}
        for cname in frame.column_names:
            if cname not in shadowed:
                cols.append(frame.column(cname))
        return TensorFrame(cols, frame.offsets)

    def warmup(
        self,
        program: Program,
        frame: TensorFrame,
        rows_level: bool = False,
        host_stage: Optional[Mapping[str, Any]] = None,
    ) -> List[str]:
        """AOT-compile the executables the map verbs will actually run
        for ``frame``, returning their fingerprints.

        "Actually" is load-bearing: the executed sizes come from the
        same :meth:`_bucket_plan` the verbs use (a cross-row program
        keeps its exact per-size shapes — bucketed signatures would be
        dead weight), and when the verbs would take the donating entry
        (fresh host frame on a donation-capable backend) the donated jit
        entry itself is lowered, so the persistent-cache key matches the
        first real dispatch.  ``host_stage`` inputs are probed on one
        row (zero rows for an empty frame) to learn the staged cell
        shape.  Not covered: the chunked-streaming path's chunk-sized
        executables (blocks past ``stream_chunk_bytes`` compile on first
        use).

        With the persistent compilation cache configured
        (``TFS_COMPILE_CACHE``), this is the cold-start path: a fresh
        process warms every executable from disk before the first block
        arrives, paying deserialization instead of XLA.  Without the
        cache it duplicates compile work — configure the cache first."""
        host_stage = _with_prelude(program, host_stage)
        verb = "map_rows" if rows_level else "map_blocks"
        if rows_level and any(
            frame.column(program.column_for_input(n)).is_ragged
            and not (host_stage and n in host_stage)
            for n in program.input_names
        ):
            raise ValidationError(
                "warmup: ragged columns are not supported — ragged "
                "map_rows executables are keyed by (rows-per-bucket, "
                "padded cell shape), which depends on the data; they "
                "compile on first use (and land in the persistent cache "
                "like everything else)."
            )
        infos = validation.check_map_inputs(
            program, frame, verb, host_staged=host_stage or ()
        )
        # staged cell shapes: probe each stage fn on (at most) one row
        staged_specs: Dict[str, Tuple[Any, Tuple[int, ...]]] = {}
        if host_stage:
            block0 = frame.block(0)
            for n in program.input_names:
                if n not in host_stage:
                    continue
                value = block0[program.column_for_input(n)][:1]
                arr = self._staged_value(host_stage[n], value, n)
                staged_specs[n] = (
                    dtypes.coerce(dtypes.from_numpy(arr.dtype)),
                    arr.shape[1:],
                )
        # mirror the dispatch exactly (the same _map_plan): blocks the
        # runtime would STREAM compile chunk-sized executables on first
        # use (documented gap) — warming their whole-block signature
        # would be dead weight — and donated entries lower to a different
        # persistent-cache key
        placement, plans, pads, donate = self._map_plan(
            program, frame, infos, host_stage, rows_level, False
        )
        exec_sizes = sorted(
            {
                pads[bi] if pads[bi] is not None else n
                for bi, n in enumerate(frame.block_sizes)
                if n > 0 and plans[bi] is None
            }
        )
        if not exec_sizes:
            # nothing block-sized will ever dispatch: every block streams
            # (chunk executables compile on first use), or the frame is
            # empty (the non-trimmed map verbs short-circuit without
            # compiling) — warming any signature would be dead weight
            return []
        run = (
            self._rows_run(program, donate)
            if rows_level
            else self._block_run(program, donate)
        )
        raw = getattr(run, "raw_jit", None) or (
            program._vmap_raw() if rows_level else program._jit_raw()
        )
        cells = {
            n: staged_specs[n]
            if n in staged_specs
            else (
                dtypes.coerce(infos[n].scalar_type),
                tuple(infos[n].cell_shape),
            )
            for n in program.input_names
        }
        fps = []
        for n_rows in exec_sizes:
            specs = {
                n: jax.ShapeDtypeStruct((n_rows,) + tuple(cell), st.np_dtype)
                for n, (st, cell) in cells.items()
            }
            fn = program.aot_compile_raw(
                raw, specs, ("aot", bool(rows_level), donate)
            )
            fps.append(fn.fingerprint)
        # (bucket size, device) grid priming: execute the SAME entry the
        # dispatch loop uses once per (bucketed size, device) on
        # zero-filled blocks, so the first real dispatch on EVERY target
        # device is a jit-cache hit (backed by the persistent cache: the
        # per-device compile is a disk fetch in a warmed process).
        # Execution, not just lowering: jax keys executables by input
        # placement, and running the entry on the target device is the
        # one way to seed that key.  Programs are pure by contract, so a
        # zeros dispatch has no effect beyond the caches; trace counting
        # is suppressed (warmup is analysis).  The grid's device axis
        # (round 10): a host-fresh pool-eligible frame primes every pool
        # device; a SHARDED-cached frame primes its shard devices; a
        # single-device cached frame primes its resident device — so a
        # cached loop's first epoch pays no compile either.
        if placement.kind == "affinity":
            prime_devs = [
                placement.devices[di]
                for di in sorted(set(placement.assignment))
            ]
        elif placement.kind == "pool":
            prime_devs = placement.devices
        elif not placement.fresh:
            dev = self._resident_device(frame)
            prime_devs = [dev] if dev is not None else []
        else:
            prime_devs = []
        if prime_devs:
            for n_rows in exec_sizes:
                zeros = {
                    n: np.zeros((n_rows,) + tuple(cell), st.np_dtype)
                    for n, (st, cell) in cells.items()
                }
                for dev in prime_devs:
                    inputs = {
                        k: jax.device_put(v, dev) for k, v in zeros.items()
                    }
                    with observability.suppress_trace_count():
                        out = run(inputs)
                    jax.block_until_ready(out)
        return fps

    def _resident_device(self, frame: TensorFrame):
        """The device a single-device cached frame's columns live on
        (first device column wins; columns are co-located by
        ``cache()``), or None for host frames.  Tolerates both jax API
        generations (``.devices()`` set vs ``.device``)."""
        for ci in frame.schema:
            data = frame.column(ci.name).data
            if not isinstance(data, jax.Array):
                continue
            devs = getattr(data, "devices", None)
            if callable(devs):
                try:
                    ds = devs()
                    if ds:
                        return next(iter(ds))
                except Exception:
                    pass
            dev = getattr(data, "device", None)
            try:
                return dev() if callable(dev) else dev
            except Exception:
                return None
        return None

    def _column_array(
        self, frame: TensorFrame, col_name: str, ci: ColumnInfo
    ):
        """A whole column as one contiguous array in its compute dtype —
        device-resident columns stay on device, host columns stay on host
        (callers ``device_put`` with their own sharding)."""
        st = dtypes.coerce(ci.scalar_type)
        data = frame.column(col_name).data
        if isinstance(data, jax.Array):
            return data if data.dtype == st.np_dtype else data.astype(st.np_dtype)
        return np.asarray(data).astype(st.np_dtype, copy=False)

    def _build_map_output(
        self,
        frame: TensorFrame,
        out_blocks: List[Dict[str, np.ndarray]],
        trim: bool,
        offsets: Optional[Sequence[int]] = None,
    ) -> TensorFrame:
        out_frame = TensorFrame.from_blocks(out_blocks)
        if trim:
            return out_frame
        # non-trimmed: append original columns not shadowed by outputs
        # (reference output schema: outputs ++ original, DebugRowOps.scala:
        # 349-372).  Divergence, by design: Spark tolerates duplicate column
        # names so the reference can emit both; our schema forbids duplicates,
        # so an output *shadows* the same-named passthrough column.
        shadowed = set(out_frame.column_names)
        cols = list(out_frame.columns)
        for cname in frame.column_names:
            if cname not in shadowed:
                cols.append(frame.column(cname))
        return TensorFrame(
            cols, offsets if offsets is not None else out_frame.offsets
        )

    # ------------------------------------------------------------- reduce --

    def _pair_call(self, program: Program, bases: Sequence[str]):
        def pairfn(left: Dict[str, Any], right: Dict[str, Any], params):
            inputs = {}
            for b in bases:
                inputs[f"{b}_1"] = left[b]
                inputs[f"{b}_2"] = right[b]
            return program.call(inputs, params)

        return pairfn

    def _tree_fold(
        self, pairfn, arrays: Dict[str, jnp.ndarray], params
    ) -> Dict[str, jnp.ndarray]:
        """Balanced deterministic tree fold over the lead axis (static size)."""
        vpair = jax.vmap(pairfn, in_axes=(0, 0, None))

        def fold(arrs: Dict[str, jnp.ndarray]):
            n = next(iter(arrs.values())).shape[0]
            if n == 0:
                raise ValidationError("cannot pairwise-fold zero rows")
            if n == 1:
                return {k: v[0] for k, v in arrs.items()}
            half = n // 2
            left = {k: v[:half] for k, v in arrs.items()}
            right = {k: v[half : 2 * half] for k, v in arrs.items()}
            combined = vpair(left, right, params)
            if n % 2:
                combined = {
                    k: jnp.concatenate([v, arrs[k][2 * half :]])
                    for k, v in combined.items()
                }
            return fold(combined)

        return fold(arrays)

    def _seq_fold(
        self, pairfn, arrays: Dict[str, jnp.ndarray], params
    ) -> Dict[str, jnp.ndarray]:
        """Left fold in row order — bit-exact reproduction of the reference's
        sequential pairwise reduction (``performReducePairwise``,
        ``DebugRowOps.scala:930-969``)."""
        init = {k: v[0] for k, v in arrays.items()}
        rest = {k: v[1:] for k, v in arrays.items()}

        def step(carry, row):
            return pairfn(carry, row, params), None

        out, _ = jax.lax.scan(step, init, rest)
        return out

    def _reduce_rows_setup(
        self, program: Program, frame: TensorFrame, mode: str
    ):
        """Shared pre-flight for reduce_rows (single-device and mesh): checks
        the pairwise contract and returns ``(bases, reduced, run)`` where
        ``run`` jit-folds a dict of block arrays down to one cell each."""
        if frame.num_rows == 0:
            raise ValidationError(
                "reduce_rows: cannot reduce an empty frame (no identity "
                "element is available for an arbitrary pairwise program)"
            )
        reduced = validation.check_reduce_rows(program, frame)
        bases = sorted(reduced)
        summaries = program.analyze(
            {
                f"{b}_{i}": (
                    dtypes.coerce(reduced[b].scalar_type),
                    tuple(reduced[b].cell_shape),
                )
                for b in bases
                for i in (1, 2)
            }
        )
        validation.check_reduce_rows_outputs(reduced, summaries)
        if mode not in ("tree", "sequential"):
            raise ValidationError(
                f"reduce_rows: unknown mode {mode!r}; use 'tree' or "
                f"'sequential'"
            )
        pairfn = self._pair_call(program, bases)
        fold = self._tree_fold if mode == "tree" else self._seq_fold

        run = program.cached_jit(
            ("reduce_rows", mode, tuple(bases)),
            lambda: lambda arrs, params: fold(pairfn, arrs, params),
        )
        return bases, reduced, run

    def reduce_rows(
        self, program: Program, frame: TensorFrame, mode: str = "tree"
    ) -> Dict[str, np.ndarray]:
        """``reduceRows`` (``DebugRowOps.scala:479-501``): pairwise-fold all
        rows of the named columns down to one row."""
        with observability.verb_span(
            "reduce_rows", frame.num_rows, frame.num_blocks
        ) as span:
            bases, reduced, run = self._reduce_rows_setup(program, frame, mode)
            span.mark("validate")
            # empty-partition guard inside (DebugRowOps:489-499); pooled
            # across local devices for host-fresh multi-block frames
            partials = self._reduce_partials(run, bases, reduced, frame, span)
            final = self._combine_partials(run, bases, partials)
            span.mark("dispatch")
            out = {b: _np(final[b]) for b in bases}
            span.mark("sync")
            return out

    def _combine_partials(
        self, run, bases, partials: List[Dict[str, jnp.ndarray]]
    ) -> Dict[str, jnp.ndarray]:
        """The ONE final-combine shape of the reduce verbs: stack every
        per-block partial in block order and re-apply ``run`` once.
        Shared by ``reduce_rows``/``reduce_blocks`` and the streaming
        incremental folds (``streaming/verbs.py``), which accumulate the
        same per-block partials window by window — so a windowed reduce
        is bit-identical to the materialized reduce over a frame with
        the same block boundaries, by construction rather than by
        numerical luck."""
        if len(partials) == 1:
            return partials[0]
        stacked = {b: jnp.stack([p[b] for p in partials]) for b in bases}
        return run(stacked)

    def _reduce_partials(
        self, run, bases, reduced, frame: TensorFrame, span
    ) -> List[Dict[str, jnp.ndarray]]:
        """Per-block partials for the reduce verbs (empty blocks skipped),
        through the one block loop (``ops/block_loop.py``).

        Pooled (host-fresh frame) or on a sharded cache's affinity
        devices, each nonempty block folds on ITS device; every partial
        then moves (async, one cell per base column) to ONE combine
        device, in block order, so the caller's final combine is
        byte-for-byte the single-device fold — same stack, same fold
        shape, bit-identical results regardless of completion order.  (A
        per-device local pre-fold would be one combine cheaper but would
        change the fold shape; bit-identity wins.)"""
        sizes = frame.block_sizes
        nonempty = [bi for bi in range(frame.num_blocks) if sizes[bi] > 0]
        sts = {b: dtypes.coerce(reduced[b].scalar_type) for b in bases}
        # base -> RESOLVED source column (feed-dict renames, round 11):
        # check_reduce_* returns the fed column's ColumnInfo, so its
        # .name is what block dicts and cache shards key on
        cols = {b: reduced[b].name for b in bases}
        partials, _ = block_loop.run_blocks(
            block_loop.place(self, frame, nonempty),
            frame,
            _ReduceWork(self, run, bases, sts, cols),
            span,
        )
        span.mark("dispatch_partials")
        return partials

    def _reduce_blocks_setup(
        self, program: Program, frame: TensorFrame, verb: str = "reduce_blocks"
    ):
        """Shared pre-flight for reduce_blocks/aggregate-style programs:
        checks the x_input contract and returns ``(bases, reduced, run)``
        where ``run`` jit-applies the block program to a dict of block
        arrays keyed by base column name."""
        if frame.num_rows == 0:
            raise ValidationError(
                f"{verb}: cannot reduce an empty frame (no identity "
                f"element is available for an arbitrary block program)"
            )
        reduced = validation.check_reduce_blocks(program, frame, verb=verb)
        bases = sorted(reduced)
        # analyze at an arbitrary static block size to validate the contract
        probe = max(frame.block_sizes) or 1
        summaries = program.analyze(
            {
                f"{b}_input": (
                    dtypes.coerce(reduced[b].scalar_type),
                    (probe,) + tuple(reduced[b].cell_shape),
                )
                for b in bases
            }
        )
        validation.check_reduce_blocks_outputs(reduced, summaries, verb=verb)

        run = program.cached_jit(
            (verb, tuple(bases)),
            lambda: lambda arrs, params: program.call(
                {f"{b}_input": arrs[b] for b in bases}, params
            ),
        )
        return bases, reduced, run

    def reduce_blocks(
        self, program: Program, frame: TensorFrame
    ) -> Dict[str, np.ndarray]:
        """``reduceBlocks`` (``DebugRowOps.scala:503-526``): phase 1 reduces
        each block to one row with the user's block program; phase 2 re-applies
        the same program once to the stacked per-block partials."""
        with observability.verb_span(
            "reduce_blocks", frame.num_rows, frame.num_blocks
        ) as span:
            bases, reduced, run = self._reduce_blocks_setup(program, frame)
            span.mark("validate")
            # empty-partition guard inside (DebugRowOps:512-522); pooled
            # across local devices for host-fresh multi-block frames
            partials = self._reduce_partials(run, bases, reduced, frame, span)
            final = self._combine_partials(run, bases, partials)
            span.mark("dispatch")
            out = {b: _np(final[b]) for b in bases}
            span.mark("sync")
            return out

    # ---------------------------------------------------------- aggregate --

    def _run_groups(
        self, vrun, batch: Dict[str, np.ndarray]
    ) -> Dict[str, jnp.ndarray]:
        """Run the vmapped block program over one [groups, size, *cell]
        bucket.  The mesh executor overrides this to shard (and pad) the
        groups axis — groups are independent under vmap, so padding is
        semantics-safe there, unlike frame rows."""
        return vrun({b: jnp.asarray(v) for b, v in batch.items()})

    def aggregate(
        self, program: Program, grouped: GroupedFrame
    ) -> TensorFrame:
        """``aggregate`` (``DebugRowOps.scala:547-592`` + ``TensorFlowUDAF``
        L601-695): apply the x_input block program once per key group.

        Groups are bucketed by cardinality and each bucket runs as ONE
        ``vmap``-ed device call over all its groups — the TPU-shaped
        replacement for Spark's shuffle + row-buffered UDAF."""
        if type(grouped) is not GroupedFrame:
            # a deferred LazyGroupedFrame handed straight to an engine
            # instance: materialise and run the eager constructor's key
            # checks (scalar rank, existence) that deferral skipped
            grouped = GroupedFrame(grouped.frame, grouped.keys)
        with observability.verb_span(
            "aggregate", grouped.frame.num_rows, grouped.frame.num_blocks
        ) as span:
            return self._aggregate_impl(program, grouped, span)

    def _aggregate_impl(
        self, program: Program, grouped: GroupedFrame, span
    ) -> TensorFrame:
        frame = grouped.frame
        reduced = validation.check_reduce_blocks(program, frame, verb="aggregate")
        bases = sorted(reduced)
        for k in grouped.keys:
            if k in reduced:
                raise ValidationError(
                    f"aggregate: column {k!r} is both a grouping key and a "
                    f"reduced column"
                )

        if frame.num_rows == 0:
            # empty-frame contract: zero groups, so an empty result frame
            # with the key columns and the program's inferred output cells
            # — the block-reduction contract is still validated (a broken
            # program must fail the same way on 0 rows as on N)
            probe_summaries = program.analyze(
                {
                    f"{b}_input": (
                        dtypes.coerce(reduced[b].scalar_type),
                        (1,) + tuple(reduced[b].cell_shape),
                    )
                    for b in bases
                }
            )
            validation.check_reduce_blocks_outputs(
                reduced, probe_summaries, verb="aggregate"
            )
            span.mark("validate_and_group_index")
            cols = []
            for kname in grouped.keys:
                kst = frame.schema[kname].scalar_type
                kdata = np.zeros((0,), dtype=kst.np_dtype)
                cols.append(
                    Column(
                        ColumnInfo(kname, kst, Shape((UNKNOWN,))), kdata
                    )
                )
            for s in probe_summaries:
                if not s.is_output:
                    continue
                cell = tuple(s.shape)
                arr = np.zeros((0,) + cell, dtype=s.scalar_type.np_dtype)
                cols.append(
                    Column(
                        ColumnInfo(
                            s.name,
                            s.scalar_type,
                            Shape(arr.shape).with_lead(UNKNOWN),
                        ),
                        arr,
                    )
                )
            return TensorFrame(cols)

        # --- device-side segmented reduction (dense monoid fast path) ---
        seg = self._aggregate_segment(program, grouped, reduced, bases, span)
        if seg is not None:
            return seg

        # --- host-side group index build (the shuffle replacement) ---
        key_cells = [np.asarray(frame.column(k).data) for k in grouped.keys]
        n = frame.num_rows
        if len(key_cells) == 1:
            uniq, inverse = np.unique(key_cells[0], return_inverse=True)
            uniq_cols = [uniq]
        else:
            stacked = np.rec.fromarrays(key_cells)
            uniq, inverse = np.unique(stacked, return_inverse=True)
            uniq_cols = [np.asarray(uniq[name]) for name in uniq.dtype.names]
        num_groups = len(uniq_cols[0])
        order = np.argsort(inverse, kind="stable")
        counts = np.bincount(inverse, minlength=num_groups)
        starts = np.zeros(num_groups, dtype=np.int64)
        np.cumsum(counts[:-1], out=starts[1:])

        # validate the block-reduction contract at the largest group size
        # (same check reduce_blocks performs; a program that does not reduce
        # its block to one cell must fail loudly, not mis-shape the output)
        probe = int(counts.max())
        summaries = program.analyze(
            {
                f"{b}_input": (
                    dtypes.coerce(reduced[b].scalar_type),
                    (probe,) + tuple(reduced[b].cell_shape),
                )
                for b in bases
            }
        )
        validation.check_reduce_blocks_outputs(
            reduced, summaries, verb="aggregate"
        )
        span.mark("validate_and_group_index")

        # --- data columns, reordered so groups are contiguous ---
        data = {}
        for b in bases:
            ci = reduced[b]
            st = dtypes.coerce(ci.scalar_type)
            data[b] = np.asarray(frame.column(ci.name).data).astype(
                st.np_dtype, copy=False
            )[order]

        vrun = program.cached_jit(
            ("aggregate_v", tuple(bases)),
            lambda: lambda arrs, params: jax.vmap(
                lambda a: program.call(
                    {f"{b}_input": a[b] for b in bases}, params
                ),
                in_axes=(0,),
            )(arrs),
        )

        # --- per-group reduction ---
        # Two device strategies (SURVEY.md P5, replacing Spark's shuffle +
        # row-buffered UDAF):
        #   * few distinct group sizes (the dense/uniform-key case): one
        #     vmapped dispatch per distinct size, gather indices built
        #     vectorized — uniform keys = ONE dispatch total;
        #   * heavy size skew: a pairwise combine tree over partials,
        #     O(log max_count) dispatches regardless of the size histogram
        #     (legal because aggregate requires an algebraic, re-applicable
        #     reduction — Operations.scala:110-126; the reference's UDAF
        #     merges partial buffers under the same assumption,
        #     DebugRowOps.scala:658-676).
        by_size: Dict[int, np.ndarray] = {}
        for size in np.unique(counts):
            by_size[int(size)] = np.nonzero(counts == size)[0]

        if len(by_size) <= 8:
            results = self._aggregate_bucketed(
                vrun, bases, data, starts, by_size, num_groups
            )
        else:
            results = self._aggregate_tree(
                vrun, bases, data, np.repeat(
                    np.arange(num_groups, dtype=np.int64), counts
                ), num_groups
            )
        span.mark("execute")

        # --- assemble one-block result: keys ++ outputs, one row per group ---
        cols: List[Column] = []
        for kname, kvals in zip(grouped.keys, uniq_cols):
            st = dtypes.from_numpy(kvals.dtype)
            info = ColumnInfo(kname, st, Shape(kvals.shape).with_lead(UNKNOWN))
            cols.append(Column(info, kvals))
        for b in bases:
            arr = results[b]
            st = dtypes.from_numpy(arr.dtype)
            info = ColumnInfo(b, st, Shape(arr.shape).with_lead(UNKNOWN))
            cols.append(Column(info, arr))
        return TensorFrame(cols)

    def _aggregate_segment(
        self, program: Program, grouped: GroupedFrame, reduced, bases, span
    ) -> Optional[TensorFrame]:
        """Device fast path (SURVEY P5's TPU equivalent): the whole keyed
        reduction runs ON DEVICE as one segmented reduction.

        Applies when the program is a recognized *monoid* per column —
        ``sum`` / ``min`` / ``max`` / ``prod`` straight over the block axis
        (detected from the jaxpr, never guessed from probing).  Keys may be
        any number of int / bool / float scalar columns.  Then, instead of
        the host ``np.unique``/argsort/gather shuffle replacement:

        * ONE device ``lax.sort`` over all key columns (lexicographic,
          stable) carrying a row-index operand — the multi-key analog of
          a stable argsort; float keys are canonicalised first (-0.0 ->
          +0.0, every NaN payload -> the NaN) so device grouping matches
          ``np.unique``, and their segment boundaries compare *bit
          patterns* so the canonical NaNs group together;
        * segment ids from the sorted-key boundaries,
          ``jax.ops.segment_{sum,min,max,prod}`` over the reordered
          columns — zero full-column host copies, zero host sort;
        * the one host sync is a scalar readback of the group count;
          ``num_segments`` (static under jit) is padded to the next power
          of two so recompiles stay logarithmic in group count;
        * outputs (group keys + reduced cells) stay device-resident.

        On a :class:`~tensorframes_tpu.parallel.MeshExecutor` the key and
        data columns are sharded over the data axis (``_place_rows``), so
        the sort, the scatter-reduce, and the compaction run as ONE
        GSPMD-partitioned computation whose cross-shard exchanges ride the
        ICI — the mesh-scale form of the reference's shuffle-grouped
        aggregation (``DebugRowOps.scala:601-695``).

        Returns None when not applicable — non-monoid programs, ragged or
        host-only columns, and key dtypes that would not survive device
        canonicalisation (int64/f64 with x64 off merge distinct groups)
        keep the exact host-indexed paths."""
        if not getattr(self, "supports_segment_aggregate", True):
            return None
        frame = grouped.frame
        n = frame.num_rows
        if n == 0 or n >= np.iinfo(np.int32).max:
            return None
        kcols = []
        for kname in grouped.keys:
            kcol = frame.column(kname)
            kst = kcol.info.scalar_type
            # keys must survive device canonicalisation unchanged: with x64
            # off, int64/f64 keys would silently truncate on device and
            # merge distinct groups (the hazard frame.cache() documents) —
            # those fall back to the host np.unique path, which is exact
            if (
                kcol.is_ragged
                or np.dtype(kst.np_dtype).kind not in "iubf"
                or dtypes.coerce(kst) is not kst
            ):
                return None
            kcols.append(kcol)
        for b in bases:
            col = frame.column(reduced[b].name)
            if col.is_ragged or not col.info.scalar_type.device_ok:
                return None
        plan = _recognize_segment_plan(program, reduced, bases)
        if plan is None:
            return None

        # mesh divisor-cliff fix (round 5): BARE-monoid plans pad the row
        # axis to a mesh multiple — pad values are the reduction identity
        # and pad keys copy row 0's key, so no group's result changes and
        # no group is added (pad iotas sort after every real row, so the
        # compaction never picks one).  Plans with a pre/post stage cannot
        # pad safely (mean reads counts; sumsq would square the pad) and
        # keep the largest-divisor sharding.
        pad_rows = self._segment_pad_rows(n) if plan.trivial_kinds else 0
        total = n + pad_rows

        def _pad_tail(arr):
            if not pad_rows:
                return arr
            return jnp.concatenate(
                [arr, jnp.repeat(arr[:1], pad_rows, axis=0)]
            )

        keys = tuple(
            self._place_rows(_pad_tail(jnp.asarray(kcol.data)))
            for kcol in kcols
        )
        iota = self._place_rows(jnp.arange(total, dtype=jnp.int32))
        # stage 1 (one dispatch): canonicalise + lexicographic sort +
        # segment-id build + group count
        sk, order, gid, newseg, count = _segment_index(keys, iota)
        num_groups = int(count)  # the one host sync (scalar)
        pad = 1 << (num_groups - 1).bit_length()
        # stage 2 (one dispatch): compact the unique key rows; the static
        # size is the power-of-two pad — like the reduce stage — so
        # executables cache logarithmically in group count
        uniqs = tuple(
            u[:num_groups] for u in _segment_compact(sk, newseg, pad)
        )
        span.mark("group_index_device")

        # stage 3 (one fused dispatch): elementwise pre stage -> key-order
        # gather -> segment scatter-reduce(s) -> per-group post stage
        # (vmapped), per the program's SegmentPlan (segment_compile.py) —
        # round 5 widens this beyond bare monoids to mean / sum-of-squares
        # / weighted-sum-style affine compositions (VERDICT r4 weak #5)
        in_cols = {}
        for b in bases:
            st = dtypes.coerce(reduced[b].scalar_type)
            arr = jnp.asarray(frame.column(reduced[b].name).data).astype(
                st.np_dtype
            )
            if pad_rows:
                ident = _monoid_identity(
                    plan.trivial_kinds[b], st.np_dtype
                )
                arr = jnp.concatenate(
                    [
                        arr,
                        jnp.full(
                            (pad_rows,) + arr.shape[1:], ident, arr.dtype
                        ),
                    ]
                )
            in_cols[f"{b}_input"] = self._place_rows(arr)
        sig = tuple(
            (nm, tuple(c.shape), str(c.dtype))
            for nm, c in sorted(in_cols.items())
        )
        run = program.cached_jit(
            ("aggregate_plan", sig, pad),
            lambda: functools.partial(_plan_apply, plan, pad),
        )
        outs_all = run(in_cols, order, gid)
        outs = {b: outs_all[b][:num_groups] for b in bases}
        span.mark("execute")

        cols: List[Column] = []
        for kcol, uniq in zip(kcols, uniqs):
            kinfo = ColumnInfo(
                kcol.info.name,
                kcol.info.scalar_type,
                Shape(uniq.shape).with_lead(UNKNOWN),
            )
            cols.append(Column(kinfo, uniq))
        for b in bases:
            arr = outs[b]
            st = dtypes.from_numpy(np.dtype(arr.dtype))
            info = ColumnInfo(b, st, Shape(arr.shape).with_lead(UNKNOWN))
            cols.append(Column(info, arr))
        return TensorFrame(cols)

    def _aggregate_bucketed(
        self, vrun, bases, data, starts, by_size, num_groups
    ) -> Dict[str, np.ndarray]:
        """One vmapped dispatch per distinct group size; gather indices are
        built with a single broadcast add per bucket (no per-group python
        loop — VERDICT r1 weak #3)."""
        out: Dict[str, Optional[np.ndarray]] = {b: None for b in bases}
        for size, gids in sorted(by_size.items()):
            gather = starts[gids][:, None] + np.arange(size, dtype=np.int64)
            batch = {b: data[b][gather] for b in bases}
            outs = self._run_groups(vrun, batch)  # base -> [len(gids), *cell]
            for b in bases:
                host = _np(outs[b])
                if out[b] is None:
                    out[b] = np.empty(
                        (num_groups,) + host.shape[1:], dtype=host.dtype
                    )
                out[b][gids] = host
        return out

    def _aggregate_tree(
        self, vrun, bases, data, gid, num_groups
    ) -> Dict[str, np.ndarray]:
        """Pairwise combine tree over row partials: each level pairs adjacent
        same-group partials and runs ONE vmapped 2-row reduction over all
        pairs (padded to a power of two so trace count stays logarithmic).
        Converges in ceil(log2(max_count)) levels for ANY size skew.

        Level 0 seeds every row as the partial ``f([x])`` — one vmapped
        singleton-block dispatch over all rows — mirroring the reference
        UDAF's init-then-merge contract (``DebugRowOps.scala:658-676``):
        partials are always *program outputs*, never raw input rows, so
        singleton groups get reduced too and every combine merges
        f-partials with f (legal for the algebraic programs aggregate
        requires)."""
        seed = self._run_groups(vrun, {b: data[b][:, None] for b in bases})
        parts = {b: _np(seed[b]) for b in bases}
        while len(gid) > num_groups:
            # stable-sorted gid -> segment starts -> pair adjacent elements
            seg_start = np.empty(len(gid), dtype=np.int64)
            seg_start[0] = 0
            new_seg = np.nonzero(np.diff(gid))[0] + 1
            starts_at = np.zeros(len(gid), dtype=np.int64)
            starts_at[new_seg] = new_seg
            np.maximum.accumulate(starts_at, out=starts_at)
            pos = np.arange(len(gid), dtype=np.int64) - starts_at
            counts = np.bincount(gid, minlength=num_groups)[gid]
            is_left = (pos % 2 == 0) & (pos + 1 < counts)
            left = np.nonzero(is_left)[0]
            right = left + 1
            passthrough = np.nonzero((pos % 2 == 0) & (pos + 1 >= counts))[0]
            p = len(left)
            # pad pair count to the next power of two: bounded trace count,
            # pad pairs are computed and discarded (independent under vmap)
            p_pad = 1 << max(p - 1, 0).bit_length() if p else 0
            li = np.concatenate([left, np.repeat(left[-1:], p_pad - p)])
            ri = np.concatenate([right, np.repeat(right[-1:], p_pad - p)])
            batch = {
                b: np.stack([parts[b][li], parts[b][ri]], axis=1)
                for b in bases
            }
            outs = self._run_groups(vrun, batch)
            new_parts = {}
            for b in bases:
                host = _np(outs[b])[:p]
                new_parts[b] = np.concatenate(
                    [host, parts[b][passthrough]]
                )
            new_gid = np.concatenate([gid[left], gid[passthrough]])
            order = np.argsort(new_gid, kind="stable")
            gid = new_gid[order]
            parts = {b: v[order] for b, v in new_parts.items()}
        # gid is sorted and exactly one partial per group remains
        return {b: parts[b] for b in bases}


def _recognize_segment_plan(program: Program, reduced, bases):
    """Compile the block program into a :class:`segment_compile.
    SegmentPlan` (elementwise pre -> segment reduce -> per-group post), or
    None when it is not expressible that way.

    Round 4 recognized only bare ``reduce_{sum,min,max,prod}`` straight
    over ``<base>_input``; the segment compiler widens this to mean,
    sum-of-squares, weighted sums, norms, and any other elementwise
    composition around the reduces, with block-size literals re-bound to
    per-group counts (``segment_compile`` module docstring).  The plan is
    memoized on the Program per input signature (three probe traces ever,
    shared by repeated aggregate calls)."""
    specs = {
        f"{b}_input": jax.ShapeDtypeStruct(
            (2,) + tuple(reduced[b].cell_shape),
            dtypes.coerce(reduced[b].scalar_type).np_dtype,
        )
        for b in bases
    }
    key = (
        "segplan",
        tuple(sorted((n, s.shape, str(s.dtype)) for n, s in specs.items())),
    )
    cache = program._derived
    if key in cache:
        return cache[key]
    cache[key] = result = segment_compile.recognize(program, specs, bases)
    return result


def _recognize_monoids(
    program: Program, reduced, bases
) -> Optional[Dict[str, str]]:
    """The strict round-3 surface: per-output monoid kinds when every
    output is a bare ``reduce_{sum,min,max,prod}`` over axis 0 applied
    DIRECTLY to its own ``<base>_input`` — None for anything wider (which
    may still run on device via the full :func:`_recognize_segment_plan`
    path)."""
    plan = _recognize_segment_plan(program, reduced, bases)
    return plan.trivial_kinds if plan is not None else None


def _monoid_identity(kind: str, dtype) -> np.ndarray:
    """The reduction identity for one monoid kind at ``dtype`` — the pad
    value that leaves a group's result unchanged (segment-aggregate mesh
    padding)."""
    dt = np.dtype(dtype)
    if kind == "sum":
        return np.zeros((), dt)
    if kind == "prod":
        return np.ones((), dt)
    if dt.kind == "f":
        return np.asarray(np.inf if kind == "min" else -np.inf, dt)
    if dt.kind == "b":
        return np.asarray(kind == "min")
    info = np.iinfo(dt)
    return np.asarray(info.max if kind == "min" else info.min, dt)


# segment-reduction dispatch shared by the plan path (one table: kinds
# come from segment_compile's _REDUCE_KINDS values)
_SEGMENT_REDUCERS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
    "prod": jax.ops.segment_prod,
}


def _plan_apply(plan, pad: int, cols, order, gid, params):
    """Aggregate fast-path stage 3 (one fused dispatch): run the plan's
    row stage on the full columns, gather into key-sorted order, scatter-
    reduce each segment input, then run the per-group post stage vmapped
    over the (power-of-two padded) group axis.  Pad groups hold reduction
    identities (and count 0 — post NaNs there are sliced off by the
    caller)."""
    pre_cols = plan.pre(cols, params)
    segs = tuple(
        _SEGMENT_REDUCERS[kind](pc[order], gid, num_segments=pad)
        for pc, kind in zip(pre_cols, plan.reduce_kinds)
    )
    counts = jax.ops.segment_sum(
        jnp.ones(gid.shape, jnp.int32), gid, num_segments=pad
    )
    return jax.vmap(
        lambda s, c: plan.post(s, c, params), in_axes=(0, 0)
    )(segs, counts)


def _canonical_key(k):
    """Float keys canonicalised so device grouping matches ``np.unique``:
    -0.0 folds into +0.0 and every NaN payload becomes THE NaN (their
    shared bit pattern then groups them in ``_boundary``)."""
    if np.dtype(k.dtype).kind == "f":
        # explicit where (not `k + 0.0`): XLA's algebraic simplifier
        # rewrites x+0 to x, which would leave -0.0 bit patterns alive
        k = jnp.where(k == 0, jnp.zeros((), k.dtype), k)
        k = jnp.where(jnp.isnan(k), jnp.asarray(jnp.nan, k.dtype), k)
    return k


def _boundary(k):
    """True where sorted key column changes value (float: bit compare, so
    the canonical NaNs form one group)."""
    if np.dtype(k.dtype).kind == "f":
        ibits = {2: jnp.int16, 4: jnp.int32, 8: jnp.int64}[
            np.dtype(k.dtype).itemsize
        ]
        b = jax.lax.bitcast_convert_type(k, ibits)
        return b[1:] != b[:-1]
    return k[1:] != k[:-1]


@jax.jit
def _segment_index(keys, iota):
    """Aggregate fast-path stage 1, one dispatch: canonicalise, stable
    lexicographic sort (all key columns + the row index as the last
    operand), boundary flags, segment ids, group count."""
    keys = tuple(_canonical_key(k) for k in keys)
    sorted_all = jax.lax.sort(
        keys + (iota,), num_keys=len(keys), is_stable=True
    )
    sk, order = sorted_all[:-1], sorted_all[-1]
    neq = _boundary(sk[0])
    for k in sk[1:]:
        neq = neq | _boundary(k)
    newseg = jnp.concatenate([jnp.ones((1,), bool), neq])
    gid = jnp.cumsum(newseg.astype(jnp.int32)) - 1
    return sk, order, gid, newseg, gid[-1] + 1


@functools.partial(jax.jit, static_argnames=("pad",))
def _segment_compact(sk, newseg, pad: int):
    """Aggregate fast-path stage 2: gather the first row of every group.
    ``pad`` is the power-of-two-padded group count (executables cache per
    (shapes, pad), not per exact count); pad entries repeat row 0 and are
    sliced off by the caller."""
    idx = jnp.nonzero(newseg, size=pad)[0]
    return tuple(k[idx] for k in sk)


_DEFAULT = Executor()


def _resolve(engine: Optional[Executor]) -> Executor:
    return engine if engine is not None else _DEFAULT


# ---------------------------------------------------------------------------
# public verb API (the tfs.* surface, core.py:10-11)
# ---------------------------------------------------------------------------


def _wrap(fn, fetches, feed_dict=None, shapes=None) -> Program:
    program = Program.wrap(fn, fetches, feed_dict)
    if shapes:
        program = program.with_shape_hints(shapes)
    return program


def _lazy_target(frame, engine):
    """The LazyFrame a map verb should append to instead of
    dispatching, or None for the eager path (``ops/planner.py``:
    the frame is lazy via ``frame.lazy()``, or ``TFS_PLAN=1`` routes
    plain frames).  An explicit ``engine=`` (mesh executors) always
    stays eager — a plan targets the default engine's dispatch
    surface."""
    if engine is not None:
        return None
    from . import planner

    return planner.maybe_lazy(frame)


def _lazy_frame(frame):
    """Materialise a LazyFrame argument for verbs that are
    materialisation points (reduce/aggregate over plain frames,
    warmup)."""
    if getattr(frame, "_tfs_lazy", False):
        from . import planner

        return planner.ensure_frame(frame)
    return frame


def map_blocks(
    fn,
    frame: TensorFrame,
    trim: bool = False,
    fetches: Optional[Sequence[str]] = None,
    feed_dict: Optional[Mapping[str, str]] = None,
    host_stage: Optional[Mapping[str, Any]] = None,
    shapes: Optional[Mapping[str, Sequence[int]]] = None,
    engine: Optional[Executor] = None,
) -> TensorFrame:
    """Apply a block-level program to every block (``tfs.map_blocks``,
    reference ``core.py:213-253``).

    ``host_stage``: input name -> host preprocessing fn (binary decode).
    ``shapes``: output name -> block-shape hint (``ShapeDescription``).

    Planned mode (``ops/planner.py``): a ``frame.lazy()`` frame — or any
    frame under ``TFS_PLAN=1`` — records the verb on a logical plan and
    returns a LazyFrame; the optimized plan executes on first
    materialisation."""
    program = _wrap(fn, fetches, feed_dict, shapes)
    lazy = _lazy_target(frame, engine)
    if lazy is not None:
        return lazy._append(
            "map_blocks", program, trim=trim, host_stage=host_stage
        )
    return _resolve(engine).map_blocks(
        program, frame, trim=trim, host_stage=host_stage
    )


def map_rows(
    fn,
    frame: TensorFrame,
    fetches: Optional[Sequence[str]] = None,
    feed_dict: Optional[Mapping[str, str]] = None,
    host_stage: Optional[Mapping[str, Any]] = None,
    shapes: Optional[Mapping[str, Sequence[int]]] = None,
    engine: Optional[Executor] = None,
) -> TensorFrame:
    """Apply a row-level program to every row (``tfs.map_rows``,
    reference ``core.py:175-211``).  ``shapes`` hints are per-row cell
    shapes.  Planned mode records the verb lazily (see
    :func:`map_blocks`)."""
    program = _wrap(fn, fetches, feed_dict, shapes)
    lazy = _lazy_target(frame, engine)
    if lazy is not None:
        return lazy._append("map_rows", program, host_stage=host_stage)
    return _resolve(engine).map_rows(program, frame, host_stage=host_stage)


def reduce_rows(
    fn,
    frame: TensorFrame,
    fetches: Optional[Sequence[str]] = None,
    mode: str = "tree",
    shapes: Optional[Mapping[str, Sequence[int]]] = None,
    engine: Optional[Executor] = None,
) -> Dict[str, np.ndarray]:
    """Pairwise-reduce all rows to one (``tfs.reduce_rows``,
    reference ``core.py:138-173``).  A LazyFrame argument is a
    materialisation point: the optimized plan executes first, then the
    reduce runs eagerly over the result."""
    program = _wrap(fn, fetches, shapes=shapes)
    if engine is None and getattr(frame, "_tfs_lazy", False):
        return frame._reduce("reduce_rows", program, mode=mode)
    return _resolve(engine).reduce_rows(
        program, _lazy_frame(frame), mode=mode
    )


def reduce_blocks(
    fn,
    frame: TensorFrame,
    fetches: Optional[Sequence[str]] = None,
    shapes: Optional[Mapping[str, Sequence[int]]] = None,
    engine: Optional[Executor] = None,
) -> Dict[str, np.ndarray]:
    """Block-reduce then combine across blocks (``tfs.reduce_blocks``,
    reference ``core.py:255-291``).  A LazyFrame argument is a
    materialisation point (see :func:`reduce_rows`)."""
    program = _wrap(fn, fetches, shapes=shapes)
    if engine is None and getattr(frame, "_tfs_lazy", False):
        return frame._reduce("reduce_blocks", program)
    return _resolve(engine).reduce_blocks(program, _lazy_frame(frame))


def aggregate(
    fn,
    grouped: GroupedFrame,
    fetches: Optional[Sequence[str]] = None,
    shapes: Optional[Mapping[str, Sequence[int]]] = None,
    engine: Optional[Executor] = None,
) -> TensorFrame:
    """Keyed algebraic aggregation (``tfs.aggregate``,
    reference ``core.py:319-336``).  Grouping a LazyFrame defers the
    one materialisation it still needs (group structure is
    data-dependent) to this call, which prunes the chain's fetches to
    exactly the key + reduced columns (``ops/planner.py`` round 19);
    the aggregate itself always runs the eager engine over the
    materialised columns, so grouping numerics cannot drift."""
    program = _wrap(fn, fetches, shapes=shapes)
    from . import planner

    if isinstance(grouped, planner.LazyGroupedFrame):
        if engine is None:
            return grouped.lazy._aggregate_terminal(
                program, grouped.keys, grouped=grouped
            )
        # explicit engine: materialise the full plan, validate keys
        grouped = GroupedFrame(grouped.frame, grouped.keys)
    if getattr(grouped.frame, "_tfs_lazy", False):
        grouped = GroupedFrame(_lazy_frame(grouped.frame), grouped.keys)
    return _resolve(engine).aggregate(program, grouped)


def warmup(
    fn,
    frame: TensorFrame,
    rows_level: bool = False,
    fetches: Optional[Sequence[str]] = None,
    feed_dict: Optional[Mapping[str, str]] = None,
    host_stage: Optional[Mapping[str, Any]] = None,
    engine: Optional[Executor] = None,
) -> List[str]:
    """AOT-compile the map-verb executables ``fn`` will run over
    ``frame`` (persistent-cache cold start; see ``Executor.warmup``).

    A LazyFrame argument first primes the PLAN's own fused-chain grid
    (``planner.warm_plan`` — the bucketed, donating, per-device entries
    the optimizer dispatches, which per-stage warmups miss), then
    materialises and warms ``fn`` over the result."""
    program = Program.wrap(fn, fetches, feed_dict)
    if engine is None and getattr(frame, "_tfs_lazy", False):
        from . import planner

        planner.warm_plan(frame)
    frame = _lazy_frame(frame)
    return _resolve(engine).warmup(
        program, frame, rows_level=rows_level, host_stage=host_stage
    )
