"""TensorFrame: the partitioned, tensor-schema'd columnar table.

The reference operates on Spark DataFrames, whose physical unit of work is the
partition: every verb materialises a partition to ``Array[Row]`` and feeds it to
the tensor runtime as one batched block (``DebugRowOps.scala:377-391``,
``TFDataOps.scala:27-59``).  The TPU-native equivalent drops the JVM row
plumbing entirely: a ``TensorFrame`` stores each column as contiguous numpy
memory (or a ragged list of cells pre-``analyze``), partitioned into *blocks*
along the row axis.  Blocks are the sharding unit — on a device mesh each block
maps to a mesh slot (SURVEY.md §2.7 P1/P2) — and columnar-contiguous storage
makes host->HBM transfer a single zero-copy ``device_put`` instead of the
reference's per-row ``TensorConverter`` appends (``datatypes.scala:93-127``).

Construction mirrors the user surfaces the reference supports: rows of python
scalars/lists (Spark ``createDataFrame`` style), column arrays, and pandas.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from . import dtypes, observability
from .dtypes import ScalarType
from .schema import ColumnInfo, Schema, SchemaError
from .shape import UNKNOWN, Shape


_log = logging.getLogger("tensorframes_tpu.frame")

# cache() skip log, one shot per distinct (columns, reasons) set: the
# answer to "why does a cached frame still stage H2D bytes?" should land
# in the log exactly once, not per verb call or per epoch
_cache_skip_logged: set = set()


def _warn_skipped_once(detail: str) -> None:
    if detail not in _cache_skip_logged:
        _cache_skip_logged.add(detail)
        _log.warning(
            "cache(): some columns stay on host and will keep paying "
            "host->device staging — %s. Pass strict=True to make this an "
            "error.",
            detail,
        )


def is_device_array(x) -> bool:
    """True for a jax array (device-resident column storage).

    Verb outputs stay on device (``jax.Array``) so chained verbs never
    round-trip through the host — the overlap design SURVEY.md §7 hard part 3
    calls for.  Host materialisation happens lazily at ``collect``/
    ``to_arrays``/``np.asarray`` time."""
    import jax

    return isinstance(x, jax.Array)


def _is_ragged(cells: Sequence[np.ndarray]) -> bool:
    if not cells:
        return False
    s0 = cells[0].shape
    return any(c.shape != s0 for c in cells)


@dataclasses.dataclass
class Column:
    """One column's physical storage.

    ``data`` is either one ndarray of shape ``(num_rows, *cell)`` (uniform) or a
    list of per-row cell ndarrays (ragged — cells disagree on shape).  Ragged
    columns correspond to the reference's un-analyzed variable-size cells
    (``TFDataOps.scala:86-103``); they must pass through ``analyze``/bucketing
    before they can reach a compiled program.
    """

    info: ColumnInfo
    data: Any  # np.ndarray | jax.Array (device-resident) | List[np.ndarray]

    @property
    def is_ragged(self) -> bool:
        if isinstance(self.data, np.ndarray):
            return self.data.dtype == object
        if getattr(self.data, "_tfs_released", False):
            # a released windowed column (ops/frame_cache.py round 18):
            # uniform by construction — only device-feedable contiguous
            # columns are ever cached, hence ever released
            return False
        return not is_device_array(self.data)

    @property
    def is_device(self) -> bool:
        """Whether the column currently lives in device memory (HBM)."""
        return is_device_array(self.data)

    def num_rows(self) -> int:
        return len(self.data)

    def cells(self) -> List[np.ndarray]:
        if is_device_array(self.data):
            return list(np.asarray(self.data))
        return list(self.data)

    def slice(self, start: int, stop: int) -> Any:
        return self.data[start:stop]


def _py_cell_shape(c) -> Optional[Tuple[int, ...]]:
    """Shape of a pure-python cell (scalar or nested list/tuple); None when
    the cell is not plain python (e.g. an ndarray)."""
    shape: List[int] = []
    while isinstance(c, (list, tuple)):
        if not c:
            return None
        shape.append(len(c))
        c = c[0]
    if isinstance(c, (bool, int, float)):
        return tuple(shape)
    return None


def _column_from_cells(
    name: str, cells: List[Any], st: Optional[ScalarType] = None
) -> Column:
    """Build a column from per-row python/numpy cells, inferring dtype and as
    much shape as possible (the role of ``ColumnInformation.getDF`` fallback
    inference, ``ColumnInformation.scala:94-138``)."""
    if not cells:
        raise SchemaError(f"column {name!r}: cannot build from zero rows")
    if st is None:
        st = dtypes.from_python_value(cells[0])
    if not st.device_ok:
        # host-only (binary/string) passthrough column
        arr = np.empty(len(cells), dtype=object)
        for i, c in enumerate(cells):
            arr[i] = c
        info = ColumnInfo(name, st, Shape((UNKNOWN,)))
        return Column(info, arr)
    # fast path: pure-python cells -> one C++ pass into the final buffer
    # (the TensorConverter/convertFast0 hot loop, SURVEY.md §7 hard part 3);
    # ragged/mis-shaped cells raise inside the packer and fall back to the
    # general path below, which handles them as a ragged column
    cell_shape = _py_cell_shape(cells[0])
    if cell_shape is not None:
        from . import native

        try:
            packed = native.pack_cells(cells, cell_shape, st.np_dtype)
        except (ValueError, TypeError):
            # any packer rejection (ragged, mis-shaped, non-plain-python
            # cells) routes to the general numpy path below
            packed = None
        if packed is not None:
            info = ColumnInfo(name, st, Shape(packed.shape).with_lead(UNKNOWN))
            return Column(info, packed)
    np_cells = [np.asarray(c, dtype=st.np_dtype) for c in cells]
    rank = np_cells[0].ndim
    for i, c in enumerate(np_cells):
        if c.ndim != rank:
            raise SchemaError(
                f"column {name!r}: row {i} has cell rank {c.ndim}, "
                f"expected {rank} (mixed ranks are not supported)"
            )
    if _is_ragged(np_cells):
        cell_shape = Shape((UNKNOWN,) * rank)
        info = ColumnInfo(name, st, cell_shape.prepend(UNKNOWN))
        return Column(info, np_cells)
    data = np.stack(np_cells) if rank else np.asarray(np_cells, dtype=st.np_dtype)
    info = ColumnInfo(name, st, Shape(data.shape).with_lead(UNKNOWN))
    return Column(info, data)


class TensorFrame:
    """Partitioned columnar table with tensor schema.

    Invariants: all columns have the same number of rows; partition offsets
    cover ``[0, num_rows]``; ``schema`` is the single source of shape/dtype
    truth (never derived lazily from Spark metadata as in the reference).
    """

    def __init__(
        self,
        columns: Sequence[Column],
        offsets: Optional[Sequence[int]] = None,
    ):
        if not columns:
            raise SchemaError("a TensorFrame needs at least one column")
        n = columns[0].num_rows()
        for c in columns:
            if c.num_rows() != n:
                raise SchemaError(
                    f"column {c.info.name!r} has {c.num_rows()} rows, "
                    f"expected {n}"
                )
        self._columns: Tuple[Column, ...] = tuple(columns)
        self._by_name = {c.info.name: c for c in self._columns}
        if len(self._by_name) != len(self._columns):
            raise SchemaError("duplicate column names")
        if offsets is None:
            offsets = (0, n)
        offsets = tuple(int(o) for o in offsets)
        if offsets[0] != 0 or offsets[-1] != n or list(offsets) != sorted(offsets):
            raise SchemaError(f"bad partition offsets {offsets} for {n} rows")
        self._offsets = offsets

    # -- constructors --------------------------------------------------------

    @staticmethod
    def from_rows(
        rows: Sequence[Mapping[str, Any]],
        schema: Optional[Schema] = None,
        num_blocks: int = 1,
    ) -> "TensorFrame":
        """Build from row dicts (the Spark ``createDataFrame(data, schema)``
        entry path used throughout the reference tests)."""
        if not rows:
            raise SchemaError("cannot build a TensorFrame from zero rows")
        names = schema.names if schema else list(rows[0].keys())
        cols = []
        for name in names:
            cells = [r[name] for r in rows]
            st = schema[name].scalar_type if schema else None
            col = _column_from_cells(name, cells, st)
            if schema is not None:
                declared = schema[name]
                # data-derived shape must refine any concrete user declaration
                if declared.block_shape.is_static:
                    col.info.block_shape.check_more_precise_than(
                        declared.block_shape, f"column {name!r}"
                    )
            cols.append(col)
        return TensorFrame(cols).repartition(num_blocks)

    @staticmethod
    def from_arrays(
        data: Mapping[str, Any], num_blocks: int = 1
    ) -> "TensorFrame":
        """Build from column name -> array (lead dim = rows)."""
        cols = []
        for name, arr in data.items():
            if isinstance(arr, (list, tuple)) and arr and isinstance(
                arr[0], np.ndarray
            ):
                cols.append(_column_from_cells(name, list(arr)))
                continue
            a = np.asarray(arr)
            if a.dtype == object or a.dtype.kind in "US":
                cols.append(_column_from_cells(name, list(a)))
                continue
            st = dtypes.from_numpy(a.dtype)
            a = a.astype(st.np_dtype, copy=False)
            info = ColumnInfo(name, st, Shape(a.shape).with_lead(UNKNOWN))
            cols.append(Column(info, a))
        return TensorFrame(cols).repartition(num_blocks)

    @staticmethod
    def from_arrow(table, num_blocks: int = 1) -> "TensorFrame":
        """Arrow Table -> frame, zero-copy where the layout allows
        (:mod:`tensorframes_tpu.io`; SURVEY.md §7's columnar ingest)."""
        from .io import table_to_frame

        return table_to_frame(table, num_blocks=num_blocks)

    def to_arrow(self):
        """Frame -> Arrow Table (inverse of :meth:`from_arrow`)."""
        from .io import frame_to_table

        return frame_to_table(self)

    @staticmethod
    def from_parquet(
        path, columns=None, num_blocks: int = 1
    ) -> "TensorFrame":
        """Read a parquet file/dir — the storage behind the reference's
        Spark DataFrames — straight into columnar frame storage."""
        from .io import read_parquet

        return read_parquet(path, columns=columns, num_blocks=num_blocks)

    def to_parquet(self, path, row_group_size: Optional[int] = None) -> None:
        from .io import write_parquet

        write_parquet(self, path, row_group_size=row_group_size)

    @staticmethod
    def from_pandas(df, num_blocks: int = 1) -> "TensorFrame":
        data = {}
        for name in df.columns:
            s = df[name]
            if s.dtype == object:
                data[name] = list(s)
            else:
                data[name] = s.to_numpy()
        return TensorFrame.from_arrays(data, num_blocks=num_blocks)

    @staticmethod
    def from_blocks(
        blocks: Sequence[Mapping[str, np.ndarray]],
        schema: Optional[Schema] = None,
    ) -> "TensorFrame":
        """Assemble from per-block column arrays (engine output path)."""
        if not blocks:
            raise SchemaError("no blocks")
        names = schema.names if schema else list(blocks[0].keys())
        offsets = [0]
        for b in blocks:
            offsets.append(offsets[-1] + len(next(iter(b.values()))))
        cols = []
        for name in names:
            parts = [b[name] for b in blocks]
            on_device = all(is_device_array(p) for p in parts)
            if not on_device:
                parts = [np.asarray(p) for p in parts]
            ranks = {p.ndim for p in parts}
            if len(ranks) != 1:
                raise SchemaError(f"column {name!r}: blocks disagree on rank")
            cell_shapes = {p.shape[1:] for p in parts}
            if len(cell_shapes) == 1 and (on_device or parts[0].dtype != object):
                if len(parts) > 1:
                    if on_device:
                        # concat on device: no host round-trip between verbs
                        import jax.numpy as jnp

                        data = jnp.concatenate(parts)
                    else:
                        data = np.concatenate(parts)
                else:
                    data = parts[0]
                st = dtypes.from_numpy(data.dtype)
                info = ColumnInfo(name, st, Shape(data.shape).with_lead(UNKNOWN))
                cols.append(Column(info, data))
            else:
                cells: List[np.ndarray] = []
                for p in parts:
                    cells.extend(list(np.asarray(p)))
                cols.append(_column_from_cells(name, cells))
        return TensorFrame(cols, offsets)

    # -- schema / metadata ---------------------------------------------------

    @property
    def schema(self) -> Schema:
        return Schema(c.info for c in self._columns)

    def with_schema(self, schema: Schema) -> "TensorFrame":
        """Attach refined metadata (the ``analyze`` output path — reference
        ``ExperimentalOperations.scala:40-46`` re-selects columns with new
        metadata; here we just swap the infos)."""
        if schema.names != [c.info.name for c in self._columns]:
            raise SchemaError("with_schema: column names must match")
        cols = [
            Column(info, c.data) for info, c in zip(schema.columns, self._columns)
        ]
        return TensorFrame(cols, self._offsets)

    # -- basic accessors -----------------------------------------------------

    @property
    def columns(self) -> Tuple[Column, ...]:
        return self._columns

    @property
    def offsets(self) -> Tuple[int, ...]:
        return self._offsets

    @property
    def num_rows(self) -> int:
        return self._columns[0].num_rows()

    @property
    def num_blocks(self) -> int:
        return len(self._offsets) - 1

    @property
    def block_sizes(self) -> List[int]:
        return [
            self._offsets[i + 1] - self._offsets[i]
            for i in range(self.num_blocks)
        ]

    @property
    def column_names(self) -> List[str]:
        return [c.info.name for c in self._columns]

    def column(self, name: str) -> Column:
        c = self._by_name.get(name)
        if c is None:
            raise SchemaError(
                f"column {name!r} not found; available: {self.column_names}"
            )
        return c

    # -- block iteration (the engine's input) --------------------------------

    def block(self, i: int) -> Dict[str, Any]:
        lo, hi = self._offsets[i], self._offsets[i + 1]
        return {c.info.name: c.slice(lo, hi) for c in self._columns}

    def blocks(self) -> Iterable[Dict[str, Any]]:
        for i in range(self.num_blocks):
            yield self.block(i)

    # -- transformations -----------------------------------------------------

    def repartition(self, num_blocks: int) -> "TensorFrame":
        """Rebalance into ``num_blocks`` near-equal blocks (Spark
        ``repartition`` analog; used to map blocks onto mesh slots).

        The block count is capped at the row count (no empty blocks are
        dealt).  Empty-frame contract: a 0-row frame always has exactly
        ONE empty block, whatever ``num_blocks`` says; the verbs then
        give it defined semantics — the non-trimmed map verbs return an
        empty frame with the program's inferred output schema (no
        compile), a trimmed map applies the program to the empty block,
        ``reduce_rows``/``reduce_blocks`` raise (no identity element for
        an arbitrary program), and ``aggregate`` returns an empty result
        frame (zero groups)."""
        n = self.num_rows
        if num_blocks < 1:
            raise SchemaError(f"num_blocks must be >= 1, got {num_blocks}")
        if n == 0:
            return TensorFrame(list(self._columns), (0, 0))
        num_blocks = min(num_blocks, n)
        base, extra = divmod(n, num_blocks)
        offsets = [0]
        for i in range(num_blocks):
            offsets.append(offsets[-1] + base + (1 if i < extra else 0))
        return TensorFrame(list(self._columns), offsets)

    def select(self, names: Sequence[str]) -> "TensorFrame":
        return TensorFrame([self.column(n) for n in names], self._offsets)

    def cache(
        self,
        device=None,
        sharded: Optional[bool] = None,
        strict: bool = False,
    ) -> "TensorFrame":
        """Pin device-feedable columns in device memory (HBM).

        The Spark ``df.cache()`` analog (the reference's demos cache the
        DataFrame before iterating, ``kmeans_demo.py``), but TPU-shaped: one
        async ``device_put`` per column, after which every verb reads the
        column from HBM with zero host->device traffic.  Columns are
        immutable, so the cached copy can never go stale.

        ``sharded`` (round 10, ``ops/frame_cache.py``): ``True`` places
        each BLOCK's column slices on that block's pool device — the
        deterministic least-loaded plan the device-pool scheduler uses —
        so the engine's affinity dispatch runs the cached frame across
        every device with zero H2D and no staging lanes.  ``None``
        follows ``TFS_CACHE_SHARDED`` (``auto``: shard exactly when the
        device pool is active); ``False`` forces the single-device
        layout.  A sharded cache KEEPS the host columns as the
        authoritative copy (eviction under ``TFS_HBM_BUDGET`` and
        fault-tolerance re-staging both rebuild from it); the shards
        ride along as ``frame._cache``.

        Stays on host either way: binary and ragged columns (host inputs
        by definition), and 64-bit columns when jax runs without x64 —
        caching those would silently truncate the stored values
        (device_put canonicalises to 32-bit) while the schema still
        claims 64; the host copy remains authoritative and verbs keep
        casting per block.  Cast the column to a 32-bit dtype first to
        cache it.  Skipped columns are logged ONCE per distinct set with
        their reasons (they are why H2D traffic persists on a "cached"
        frame); ``strict=True`` raises instead.

        Transfers are issued through ``ops.prefetch.stage_columns`` — the
        engine's one transfer-issue policy point — so the per-column
        ``device_put`` calls queue back to back on the link.  Once cached,
        the verbs' prefetch/donation machinery treats the columns as
        shared device state: never streamed, never donated
        (``ops/prefetch.py``'s safety contract)."""
        from .ops import frame_cache, prefetch

        host: Dict[str, Any] = {}
        skipped: Dict[str, str] = {}
        for c in self._columns:
            st = c.info.scalar_type
            if c.is_device:
                continue  # already resident
            if c.is_ragged:
                skipped[c.info.name] = (
                    "ragged (variable cell shapes; analyze/bucket first)"
                )
            elif not st.device_ok:
                skipped[c.info.name] = (
                    f"host-only scalar type {st.name} (binary/string)"
                )
            elif dtypes.coerce(st) is not st:
                skipped[c.info.name] = (
                    f"{st.name} would canonicalise to "
                    f"{dtypes.coerce(st).name} on device (jax x64 is off; "
                    f"cast the column first)"
                )
            else:
                host[c.info.name] = c.data
        if skipped:
            detail = "; ".join(
                f"{name}: {why}" for name, why in sorted(skipped.items())
            )
            if strict:
                raise SchemaError(
                    f"cache(strict=True): {len(skipped)} column(s) cannot "
                    f"be cached on device — {detail}"
                )
            _warn_skipped_once(detail)
        if device is not None and sharded:
            raise SchemaError(
                "cache(): device= pins every column on ONE device and "
                "sharded=True requests block-affinity placement across "
                "the pool — pass one or the other."
            )
        devs = (
            frame_cache.shard_devices(sharded)
            if device is None and sharded is not False
            else None
        )
        # the placement, whichever layout runs.  The span times the
        # calls: device_put returns before the bytes have landed, and
        # whoever first waits on the columns pays the rest
        with observability.span(
            "cache.place", "cache",
            bytes=sum(frame_cache.array_nbytes(v) for v in host.values()),
            devices=len(devs) if devs else 1,
        ):
            if devs:
                # windowed frames (streaming/reader.py sets
                # _host_windowed) have no durable host authority — the
                # stream moves past the window — so their budget
                # evictions must spill shard bytes to TFS_SPILL_DIR
                # instead of dropping them (ops/frame_cache.py)
                spill = None
                if getattr(self, "_host_windowed", False):
                    from .streaming import spill as _spill

                    spill = _spill.store_if_configured()
                cache = frame_cache.build(
                    self, sorted(host), devices=devs, spill=spill
                )
                if cache is not None:
                    out = TensorFrame(list(self._columns), self._offsets)
                    out._host_windowed = getattr(
                        self, "_host_windowed", False
                    )
                    frame_cache.attach(out, cache)
                    if spill is not None and (
                        frame_cache.release_host_enabled()
                    ):
                        # round 18: a windowed frame's bytes now all
                        # have a durable home (HBM shard, or disk via
                        # the spill-backed eviction path), so the host
                        # copies stop pinning RAM — the frame object
                        # stays fully usable through the lazy
                        # spill-backed stand-ins
                        frame_cache.release_host_columns(out)
                    return out
            staged = prefetch.stage_columns(host, device)
        cols = [
            Column(c.info, staged[c.info.name])
            if c.info.name in staged
            else c
            for c in self._columns
        ]
        return TensorFrame(cols, self._offsets)

    def uncache(self) -> "TensorFrame":
        """Materialise device-resident columns back to host numpy; a
        sharded cache (``cache(sharded=True)``) is released — its shards
        drop out of the ``TFS_HBM_BUDGET`` accounting — and the
        authoritative host columns carry over unchanged.  Released
        windowed columns (round 18) re-materialise to real host arrays
        BEFORE the cache (and its spill files) goes away."""
        from .ops import frame_cache

        cache = getattr(self, "_cache", None)
        for c in self._columns:
            if frame_cache.is_released(c.data):
                # in place: the data objects are shared with the frame
                # this one was derived from, which must not be left
                # pointing at a released cache
                c.data = np.asarray(c.data)
        if cache is not None:
            cache.release()
            frame_cache.attach(self, None)
        cols = [
            Column(c.info, np.asarray(c.data)) if c.is_device else c
            for c in self._columns
        ]
        return TensorFrame(cols, self._offsets)

    def group_by(self, *keys: str):
        """Group rows by key columns for ``aggregate`` (Spark ``groupBy``)."""
        from .ops.engine import GroupedFrame

        return GroupedFrame(self, keys)

    def lazy(self) -> "Any":
        """Switch this frame into *planned* mode (``ops/planner.py``,
        round 14): verbs called on the returned LazyFrame append to a
        logical plan instead of dispatching, and the optimized plan —
        adjacent maps fused into one dispatch, dead columns pruned
        before staging, twice-consumed subplans auto-cached sharded —
        executes on first materialisation (``collect``/``to_arrays``/…,
        a reduce verb, ``aggregate``).  ``tfs.explain`` renders the
        plan.  Eager execution (calling verbs on ``self``) stays the
        default and is bit-identical.

        One shared plan root per frame object: repeated ``lazy()``
        calls return the same node, so chains built from separate
        ``lazy()`` calls still count as consumers of one subplan (the
        auto-cache trigger)."""
        from .ops.planner import root_for

        return root_for(self)

    # -- materialisation -----------------------------------------------------

    def collect(self) -> List[Dict[str, Any]]:
        """All rows as dicts of python/numpy values (Spark ``collect``)."""
        out = []
        cells = {c.info.name: c.cells() for c in self._columns}
        for i in range(self.num_rows):
            out.append({name: cs[i] for name, cs in cells.items()})
        return out

    def to_arrays(self) -> Dict[str, Any]:
        out = {}
        for c in self._columns:
            if c.is_ragged:
                out[c.info.name] = c.cells()
            else:
                out[c.info.name] = c.data
        return out

    def to_pandas(self):
        import pandas as pd

        data = {}
        for c in self._columns:
            if c.is_ragged or c.info.cell_shape.rank > 0:
                data[c.info.name] = c.cells()
            elif c.is_device:
                data[c.info.name] = np.asarray(c.data)
            else:
                data[c.info.name] = c.data
        return pd.DataFrame(data)

    def __repr__(self):
        return (
            f"TensorFrame[{self.num_rows} rows x {len(self._columns)} cols, "
            f"{self.num_blocks} block(s)]\n{self.schema.explain()}"
        )
