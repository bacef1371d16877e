"""Fused verb pipelines: a chain of verbs compiled into ONE XLA dispatch.

The per-verb engine (``engine.py``) dispatches each verb separately: a chained
``map_blocks_trimmed -> reduce_blocks`` step — the body of every iterative
driver (logreg, kmeans) — pays one dispatch per verb plus a host readback of
the reduced scalars per step.  That is exactly the per-call overhead the
reference measures in its perf suite
(``/root/reference/src/test/scala/org/tensorframes/perf/PerformanceSuite.scala:14-26``)
and works around by fusing compute + pre-aggregation into a single TF graph
(``/root/reference/src/main/python/tensorframes_snippets/kmeans_demo.py:101-168``).
The TPU-native answer is stronger than graph fusion: *the whole verb chain is
one jit trace*, so XLA fuses across verb boundaries, intermediates never leave
HBM, and an iterative driver can run its entire loop on device
(``lax.scan``) with parameters carried between steps — one dispatch and one
readback for K steps, instead of 2K dispatches and K scalar syncs.

Usage::

    pipe = (tfs.pipeline(frame)
            .map_blocks(grad_prog, trim=True)     # block -> 1-row partials
            .reduce_blocks(sum_prog)              # cross-block sum
            .then(sgd_update))                    # traced post-processing
    row  = pipe.run()                             # ONE dispatch; device dict
    out  = pipe.collect()                         # run + host materialise

    # iterative driver: K steps in ONE dispatch, params stay on device
    finals, hist = pipe.iterate(50, carry={"w": "w", "b": "b"},
                                collect=("loss",))

Semantics match the eager verbs exactly (parity-tested in
``tests/test_pipeline.py``); the differences are deliberate and validated at
build time:

* host-only (binary/string) and ragged columns cannot flow *through* a fused
  trace — a program referencing one is rejected with a pointer at the eager
  verbs (host_stage decode belongs outside a fused chain by construction);
  untouched host columns of the source frame are re-attached to map-terminal
  outputs on the host side, where row identity is preserved.
* ``aggregate`` is not fusable (its group structure is data-dependent); use
  the eager verb.

Mesh composition: ``tfs.pipeline(frame, engine=MeshExecutor(mesh))`` runs
the SAME fused chain with the source columns sharded over the executor's
data axis and the whole frame treated as ONE logical block (the mesh
executor's ``global`` semantics) — GSPMD partitions the fused executable
and lowers the reduce stages' cross-shard combines onto ICI collectives.
Size row counts as a multiple of the data axis: other counts degrade to
the largest-divisor sub-mesh (``_shard_for``'s logged fallback — padding
is not semantics-safe for arbitrary cross-row programs).  Per-block
("partition") semantics stay with the eager ``MeshExecutor`` verbs;
``mode="per_block"`` executors are rejected here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import cancellation, dtypes, observability
from ..frame import TensorFrame, is_device_array
from ..program import Program
from ..schema import ColumnInfo, Schema
from ..shape import Shape, UNKNOWN
from ..analysis import rowdep as analysis
from . import (
    block_loop,
    bucketing,
    device_pool,
    fault_tolerance,
    frame_cache,
    prefetch,
    validation,
)
from .engine import _DEFAULT
from .validation import ValidationError


@dataclasses.dataclass(frozen=True)
class _Stage:
    kind: str  # map_blocks | map_rows | reduce_blocks | reduce_rows | then
    program: Optional[Program] = None
    trim: bool = False
    mode: str = "tree"
    fn: Optional[Callable] = None
    # build-time bookkeeping
    reduced_bases: Tuple[str, ...] = ()


class _SchemaView:
    """Duck-typed stand-in for a TensorFrame in the validation helpers (they
    only touch ``.schema``)."""

    def __init__(self, infos: Mapping[str, ColumnInfo]):
        self.schema = Schema(list(infos.values()))


def _block_info(name: str, st, cell_shape) -> ColumnInfo:
    return ColumnInfo(name, st, Shape(cell_shape).prepend(UNKNOWN))


def analyzed_outputs(
    program: Program,
    infos: Mapping[str, ColumnInfo],
    cell: bool,
    verb: str = "pipeline",
) -> Dict[str, ColumnInfo]:
    """Shape-infer a map stage's outputs from its input ColumnInfos —
    the schema-tracking step shared by the fused Pipeline builders and
    the lazy planner's composed-program fusion (``ops/planner.py``).
    ``cell``: the program is row-level (map_rows), so specs and output
    shapes are per-cell."""
    specs = {}
    for n, ci in infos.items():
        st = dtypes.coerce(ci.scalar_type)
        shape = (
            tuple(ci.cell_shape)
            if cell
            else (UNKNOWN,) + tuple(ci.cell_shape)
        )
        specs[n] = (st, Shape(shape))
    outs: Dict[str, ColumnInfo] = {}
    for s in program.analyze(specs):
        if s.is_output:
            block_shape = s.shape.prepend(UNKNOWN) if cell else s.shape
            if not cell and block_shape.rank == 0:
                raise ValidationError(
                    f"{verb}.map_blocks: output {s.name!r} is a scalar; "
                    f"block outputs need a lead row axis."
                )
            outs[s.name] = ColumnInfo(s.name, s.scalar_type, block_shape)
    return outs


def _reduce_src_cols(program, bases, suffix: str) -> Dict[str, str]:
    """base -> source chain column for a terminal reduce stage,
    honouring feed-dict renames (round 11): ``inputs={"x_input":
    "data"}`` folds the chain's ``data`` column into output ``x``."""
    out = {}
    for b in bases:
        n = f"{b}{suffix}"
        col = program.column_for_input(n)
        out[b] = b if col == n else col
    return out


class Pipeline:
    """A lazy verb chain over one frame; built by :func:`pipeline`.

    Builder methods return a NEW Pipeline (the receiver stays valid), so
    chains can fork.  Compilation happens at the first ``run``/``collect``/
    ``iterate`` and is cached on the terminal Pipeline object.

    Forks share STAGE STATE, not just structure: stages hold the caller's
    ``Program`` objects by reference, and ``iterate``'s resume contract
    updates those programs' params in place — deliberately, so the
    caller's own handle (and any sibling fork) continues from the trained
    state, exactly like calling ``program.update_params`` yourself.  If a
    fork must iterate from pristine params, give it its own ``Program``
    (``Program(graphdef, **initial_params)``) rather than sharing one
    across forks (ADVICE r4).
    """

    def __init__(
        self,
        frame: TensorFrame,
        stages: Tuple[_Stage, ...] = (),
        visible: Optional[Dict[str, ColumnInfo]] = None,
        from_source: Optional[Dict[str, bool]] = None,
        row_stage: bool = False,
        engine=None,
    ):
        self._frame = frame
        self._stages = stages
        # a MeshExecutor engine switches the chain to mesh-global
        # semantics: one logical block, rows sharded over the data axis
        self._engine = engine
        if visible is None:
            visible = {}
            from_source = {}
            for c in frame.columns:
                if c.info.scalar_type.device_ok and not c.is_ragged:
                    visible[c.info.name] = c.info
                    from_source[c.info.name] = True
        self._visible = visible
        self._from_source = from_source or {}
        self._row_stage = row_stage  # terminal produces a row, not a frame
        # keyed by donate flag: a host-sourced frame stages fresh entry
        # buffers per call and may donate them; a cached frame must not
        self._compiled: Dict[bool, Any] = {}
        self._iter_compiled: Dict[Any, Any] = {}
        # device-pool per-block executable (map-terminal chains), keyed
        # by donate flag like _compiled; _pool_proofs memoizes the
        # chain-level row-independence proofs bucket padding is gated on
        self._pool_compiled: Dict[bool, Any] = {}
        self._pool_proofs: Dict[Any, bool] = {}

    # ------------------------------------------------------------ builders --

    def _require_frame_stage(self, verb: str) -> None:
        if self._row_stage:
            raise ValidationError(
                f"pipeline.{verb}: the chain already ended in a row-producing "
                f"stage (reduce/then); only then/run/collect/iterate may "
                f"follow."
            )

    def _check_inputs(
        self, program: Program, verb: str
    ) -> Dict[str, ColumnInfo]:
        infos: Dict[str, ColumnInfo] = {}
        source_schema = self._frame.schema
        for n in program.input_names:
            col = program.column_for_input(n)
            if col in self._visible:
                infos[n] = self._visible[col]
                continue
            if col in source_schema:
                ci = source_schema[col]
                fcol = self._frame.column(col)
                if not ci.scalar_type.device_ok or fcol.is_ragged:
                    why = (
                        "is host-only (binary/string)"
                        if not ci.scalar_type.device_ok
                        else "is ragged/un-analyzed"
                    )
                    raise ValidationError(
                        f"pipeline.{verb}: column {col!r} {why} and cannot "
                        f"flow through a fused device trace. Use the eager "
                        f"verb (tfs.{verb}) with host_stage/analyze for "
                        f"this column."
                    )
                raise ValidationError(
                    f"pipeline.{verb}: column {col!r} was dropped by an "
                    f"earlier trim stage (trim=True replaces the block with "
                    f"the program outputs only). Available here: "
                    f"{sorted(self._visible)}."
                )
            raise ValidationError(
                f"pipeline.{verb}: program input {n!r} requests column "
                f"{col!r}, which is not available at this point in the "
                f"chain. Available: {sorted(self._visible)}."
            )
        return infos

    def _analyzed_outputs(
        self, program: Program, infos: Mapping[str, ColumnInfo], cell: bool
    ) -> Dict[str, ColumnInfo]:
        """Shape-infer a map stage's outputs to keep schema tracking exact."""
        return analyzed_outputs(program, infos, cell, verb="pipeline")

    def map_blocks(self, fn, trim: bool = False, **kw) -> "Pipeline":
        """Append a block-level map (``tfs.map_blocks``; trim=True for
        ``map_blocks_trimmed``)."""
        self._require_frame_stage("map_blocks")
        program = Program.wrap(fn, **kw)
        infos = self._check_inputs(program, "map_blocks")
        outs = self._analyzed_outputs(program, infos, cell=False)
        visible = dict(outs) if trim else {**self._visible, **outs}
        from_source = (
            {k: False for k in outs}
            if trim
            else {**self._from_source, **{k: False for k in outs}}
        )
        return Pipeline(
            self._frame,
            self._stages + (_Stage("map_blocks", program, trim=trim),),
            visible,
            from_source,
            engine=self._engine,
        )

    def map_blocks_trimmed(self, fn, **kw) -> "Pipeline":
        return self.map_blocks(fn, trim=True, **kw)

    def map_rows(self, fn, **kw) -> "Pipeline":
        """Append a row-level map (``tfs.map_rows``, vmapped in the trace)."""
        self._require_frame_stage("map_rows")
        program = Program.wrap(fn, **kw)
        infos = self._check_inputs(program, "map_rows")
        outs = self._analyzed_outputs(program, infos, cell=True)
        visible = {**self._visible, **outs}
        from_source = {**self._from_source, **{k: False for k in outs}}
        return Pipeline(
            self._frame,
            self._stages + (_Stage("map_rows", program),),
            visible,
            from_source,
            engine=self._engine,
        )

    def reduce_blocks(self, fn, **kw) -> "Pipeline":
        """Append the terminal block reduction (``tfs.reduce_blocks``)."""
        self._require_frame_stage("reduce_blocks")
        if self._frame.num_rows == 0:
            raise ValidationError(
                "pipeline.reduce_blocks: cannot reduce an empty frame (no "
                "identity element is available for an arbitrary block "
                "program)"
            )
        program = Program.wrap(fn, **kw)
        view = _SchemaView(self._visible)
        reduced = validation.check_reduce_blocks(
            program, view, verb="pipeline.reduce_blocks"
        )
        bases = tuple(sorted(reduced))
        probe = max(self._frame.block_sizes) or 1
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
            reduced, summaries, verb="pipeline.reduce_blocks"
        )
        return Pipeline(
            self._frame,
            self._stages
            + (_Stage("reduce_blocks", program, reduced_bases=bases),),
            self._visible,
            self._from_source,
            row_stage=True,
            engine=self._engine,
        )

    def reduce_rows(self, fn, mode: str = "tree", **kw) -> "Pipeline":
        """Append the terminal pairwise reduction (``tfs.reduce_rows``)."""
        self._require_frame_stage("reduce_rows")
        if self._frame.num_rows == 0:
            raise ValidationError(
                "pipeline.reduce_rows: cannot reduce an empty frame (no "
                "identity element is available for an arbitrary pairwise "
                "program)"
            )
        if mode not in ("tree", "sequential"):
            raise ValidationError(
                f"pipeline.reduce_rows: unknown mode {mode!r}; use 'tree' or "
                f"'sequential'"
            )
        program = Program.wrap(fn, **kw)
        view = _SchemaView(self._visible)
        reduced = validation.check_reduce_rows(program, view)
        bases = tuple(sorted(reduced))
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
        return Pipeline(
            self._frame,
            self._stages
            + (_Stage("reduce_rows", program, mode=mode, reduced_bases=bases),),
            self._visible,
            self._from_source,
            row_stage=True,
            engine=self._engine,
        )

    def then(self, fn: Callable) -> "Pipeline":
        """Append traced post-processing of the reduced row.

        ``fn(row, params)`` receives the reduced outputs (name -> array) and
        the union of all stage-program params (name -> value) and returns a
        dict of named outputs — the place for parameter updates and derived
        scalars, fused into the same dispatch."""
        if not self._row_stage:
            raise ValidationError(
                "pipeline.then: requires a reduce stage first (then() "
                "post-processes the reduced row)."
            )
        seen: Dict[str, int] = {}
        for i, st in enumerate(self._stages):
            if st.program is not None:
                for pname in st.program.param_names:
                    if pname in seen and seen[pname] != i:
                        raise ValidationError(
                            f"pipeline.then: param name {pname!r} exists on "
                            f"multiple stages; rename one to disambiguate."
                        )
                    seen[pname] = i
        return Pipeline(
            self._frame,
            self._stages + (_Stage("then", fn=fn),),
            self._visible,
            self._from_source,
            row_stage=True,
            engine=self._engine,
        )

    # --------------------------------------------------------------- trace --

    def _needed_source_cols(self) -> List[str]:
        """Source columns the trace must receive: every referenced source
        column, plus — for map-terminal chains — every still-visible source
        column (they pass through into the output frame)."""
        needed = set()
        for st in self._stages:
            if st.program is None:
                continue
            if st.kind in ("map_blocks", "map_rows"):
                refs = [
                    st.program.column_for_input(n)
                    for n in st.program.input_names
                ]
            else:
                # reduce stages read their feed-RESOLVED source columns
                # (round 11): the bases alone would prune a renamed
                # source out of the staged trace inputs
                suffix = "_input" if st.kind == "reduce_blocks" else "_1"
                refs = list(
                    _reduce_src_cols(
                        st.program, st.reduced_bases, suffix
                    ).values()
                )
            needed.update(refs)
        if not self._row_stage:
            needed.update(
                k for k, src in self._from_source.items() if src
            )
        # keep only true source columns (later stages may reference derived)
        src_names = {
            c.info.name
            for c in self._frame.columns
            if c.info.scalar_type.device_ok and not c.is_ragged
        }
        return sorted(needed & src_names)

    @property
    def _mesh_mode(self) -> bool:
        """True when the chain runs mesh-global: one logical block, rows
        sharded over the engine's data axis (duck-typed MeshExecutor)."""
        return self._engine is not None and hasattr(self._engine, "mesh")

    def _body(self, cols: Dict[str, Any], params_list: List[Dict]) -> Any:
        """The traced chain: cols are full source columns; returns either the
        final row dict or the list of per-block column dicts."""
        frame = self._frame
        src_schema = frame.schema
        if self._mesh_mode:
            # mesh-global semantics: the whole frame is ONE logical block
            # (GSPMD partitions the trace over the sharded rows)
            ranges = [(0, frame.num_rows)]
        else:
            ranges = [
                (frame.offsets[i], frame.offsets[i + 1])
                for i in range(frame.num_blocks)
            ]
        blocks: List[Dict[str, Any]] = []
        for lo, hi in ranges:
            # empty blocks flow through map stages (eager parity: map verbs
            # emit one output block per input block, empty included); the
            # reduce stages skip them below, like the engine's guards
            blk = {}
            for name, arr in cols.items():
                st = dtypes.coerce(src_schema[name].scalar_type)
                a = arr[lo:hi]
                if a.dtype != st.np_dtype:
                    a = a.astype(st.np_dtype)
                blk[name] = a
            blocks.append(blk)

        row: Optional[Dict[str, Any]] = None
        for st, params in zip(self._stages, params_list):
            if st.kind in ("map_blocks", "map_rows"):
                blocks = [
                    self._map_stage_block(st, blk, params) for blk in blocks
                ]
            elif st.kind == "reduce_blocks":
                program, bases = st.program, list(st.reduced_bases)
                srcs = _reduce_src_cols(program, bases, "_input")
                partials = [
                    program.call(
                        {f"{b}_input": blk[srcs[b]] for b in bases}, params
                    )
                    for blk in blocks
                    if next(iter(blk.values())).shape[0] > 0
                ]
                if not partials:
                    raise ValidationError(
                        "pipeline.reduce_blocks: every block is empty at "
                        "the reduce stage; nothing to reduce."
                    )
                if len(partials) == 1:
                    row = partials[0]
                else:
                    stacked = {
                        f"{b}_input": jnp.stack([p[b] for p in partials])
                        for b in bases
                    }
                    row = program.call(stacked, params)
            elif st.kind == "reduce_rows":
                program, bases = st.program, list(st.reduced_bases)
                srcs = _reduce_src_cols(program, bases, "_1")
                pairfn = _DEFAULT._pair_call(program, bases)
                fold = (
                    _DEFAULT._tree_fold
                    if st.mode == "tree"
                    else _DEFAULT._seq_fold
                )
                partials = [
                    fold(pairfn, {b: blk[srcs[b]] for b in bases}, params)
                    for blk in blocks
                    if next(iter(blk.values())).shape[0] > 0
                ]
                if not partials:
                    raise ValidationError(
                        "pipeline.reduce_rows: every block is empty at "
                        "the reduce stage; nothing to reduce."
                    )
                if len(partials) == 1:
                    row = partials[0]
                else:
                    stacked = {
                        b: jnp.stack([p[b] for p in partials]) for b in bases
                    }
                    row = fold(pairfn, stacked, params)
            elif st.kind == "then":
                merged: Dict[str, Any] = {}
                for stg, p in zip(self._stages, params_list):
                    if stg.program is not None:
                        merged.update(p)
                out = st.fn(row, merged)
                if not isinstance(out, Mapping):
                    raise ValidationError(
                        "pipeline.then: fn must return a dict of named "
                        f"outputs, got {type(out).__name__}"
                    )
                row = {k: jnp.asarray(v) for k, v in out.items()}
            else:  # pragma: no cover
                raise AssertionError(st.kind)
        return row if self._row_stage else blocks

    def _map_stage_block(
        self, st: _Stage, blk: Dict[str, Any], params: Mapping[str, Any]
    ) -> Dict[str, Any]:
        """One map stage applied to ONE block dict (traced) — shared by the
        fused whole-frame body and the device-pool per-block body, so the
        two execution paths cannot drift semantically."""
        if st.kind == "map_blocks":
            n_rows = len(next(iter(blk.values())))
            inputs = {
                n: blk[st.program.column_for_input(n)]
                for n in st.program.input_names
            }
            outs = st.program.call(inputs, params)
            if not st.trim:
                for name, v in outs.items():
                    if v.ndim == 0 or v.shape[0] != n_rows:
                        raise ValidationError(
                            f"pipeline.map_blocks: output {name!r} "
                            f"has shape {v.shape} but the block has "
                            f"{n_rows} rows; use trim=True to change "
                            f"the row count."
                        )
                return {
                    **{k: v for k, v in blk.items() if k not in outs},
                    **outs,
                }
            counts = {
                v.shape[0] if v.ndim else None for v in outs.values()
            }
            if len(counts) != 1 or None in counts:
                raise ValidationError(
                    f"pipeline.map_blocks_trimmed: outputs "
                    f"disagree on row count: "
                    f"{ {k: v.shape for k, v in outs.items()} }"
                )
            return dict(outs)
        if st.kind == "map_rows":
            program = st.program
            inputs = {
                n: blk[program.column_for_input(n)]
                for n in program.input_names
            }
            outs = jax.vmap(
                lambda ins, p=params, pr=program: pr.call(ins, p),
                in_axes=(0,),
            )(inputs)
            return {
                **{k: v for k, v in blk.items() if k not in outs},
                **outs,
            }
        raise AssertionError(st.kind)  # pragma: no cover

    def _block_chain(
        self, cols_blk: Dict[str, Any], params_list: List[Dict]
    ) -> Dict[str, Any]:
        """The map-stage chain over ONE block (traced): the device-pool
        per-block body.  Mirrors ``_body``'s per-block handling exactly —
        same entry casts, same stage application via
        :meth:`_map_stage_block`."""
        src_schema = self._frame.schema
        blk = {}
        for name, a in cols_blk.items():
            st = dtypes.coerce(src_schema[name].scalar_type)
            blk[name] = a if a.dtype == st.np_dtype else a.astype(st.np_dtype)
        for st_, params in zip(self._stages, params_list):
            blk = self._map_stage_block(st_, blk, params)
        return blk

    def _params_list(self) -> List[Dict[str, Any]]:
        return [
            dict(st.program._params) if st.program is not None else {}
            for st in self._stages
        ]

    def with_frame(self, frame: TensorFrame) -> "Pipeline":
        """Re-bind this chain to a new source frame with the same
        column layout — the streaming window loop's entry point
        (``streaming.run_pipeline`` runs ``pipe.with_frame(window).
        run()`` per window).

        Stages are shared BY REFERENCE: their ``Program`` objects — and
        therefore every ``cached_jit``/AOT executable those programs
        hold — stay hot across windows, which is what makes a
        per-window pipeline cheap (full windows share one row count, so
        one executable serves the stream).  The per-Pipeline compiled
        plans are deliberately NOT carried over: they may close over the
        bound frame, and a stale closure would silently read the old
        window's data."""
        if frame.column_names != self._frame.column_names:
            raise ValidationError(
                f"pipeline.with_frame: the new frame's columns "
                f"{frame.column_names} do not match the chain's source "
                f"columns {self._frame.column_names}"
            )
        return Pipeline(
            frame,
            self._stages,
            dict(self._visible),
            dict(self._from_source),
            self._row_stage,
            self._engine,
        )

    # ----------------------------------------------------------- execution --

    def run(self):
        """Compile (once) and dispatch the fused chain — ONE jit call.

        On the fused (default) path, returns device-resident results — a
        dict of arrays for row-terminal chains, a TensorFrame with device
        columns for map-terminal chains — with no host sync here;
        materialise with ``collect()`` / ``np.asarray`` when the values
        are needed.

        Device pool (``ops/device_pool.py``): a MAP-terminal chain over a
        host-fresh multi-block frame dispatches the same fused per-block
        body across all local devices instead of one whole-frame trace —
        blocks are independent, so the chain parallelizes exactly like
        the eager map verbs.  On THAT path the columns come back
        host-resident, assembled in block order, and the call
        synchronizes on the last block (overlapped per-block readback) —
        the pool trades the async device-resident contract for
        cross-device parallelism.  Row-terminal chains always keep the
        single fused dispatch: their cross-block combine shape IS the
        executable."""
        if not self._stages:
            raise ValidationError("pipeline.run: empty pipeline (no stages)")
        plan = self._pool_plan()
        if plan is not None:
            return self._run_pooled(*plan)
        with observability.verb_span(
            "pipeline", self._frame.num_rows, self._frame.num_blocks
        ) as span:
            cols, donate = self._entry_cols()
            if donate not in self._compiled:
                self._compiled[donate] = jax.jit(
                    lambda cols, params_list: self._body(cols, params_list),
                    **({"donate_argnums": (0,)} if donate else {}),
                )
            span.mark("validate")
            span.annotate("donate_entry", donate)
            out = self._compiled[donate](cols, self._params_list())
            del cols  # staged entry buffers: donated or dead either way
            span.mark("dispatch")
            if self._row_stage:
                return out
            frame = TensorFrame.from_blocks(out)
            # host-only / ragged source columns pass through unchanged when
            # the chain preserves row identity (no trim stage)
            if not any(s.trim for s in self._stages):
                extra = [
                    c
                    for c in self._frame.columns
                    if c.info.name not in frame.column_names
                    and c.info.name not in self._visible
                ]
                if extra:
                    frame = TensorFrame(
                        list(frame.columns) + extra, frame.offsets
                    )
            return frame

    def _pool_plan(self):
        """``(devices, entry layout, cache)`` for a pooled run, or None
        to take the fused whole-frame dispatch.  Pooling needs: a
        map-terminal chain (map stages only), no mesh engine, >= 2
        blocks, and a fully host-resident entry set.  Two ways in:

        * a host-fresh frame with >= 2 pool devices — the round-8 plan
          (per-device staging lanes, donated entry buffers);
        * a SHARDED-cached frame (``ops/frame_cache.py``; its host
          columns stay authoritative, so the entry set still reads as
          host-resident) — the run follows the cache's own device set
          and block-affinity assignment, pool knob or not, with no
          lanes, no donation and no H2D for resident shards.

        A single-device (round-2) cached frame still bypasses pooling:
        its columns live on ONE device and splitting them would shuffle
        HBM.  The knob and layout are resolved ONCE here and threaded
        through the whole pooled run, so a mid-call env flip cannot
        yield an inconsistent plan."""
        if (
            self._row_stage
            or self._mesh_mode
            or self._frame.num_blocks < 2
            or any(
                st.kind not in ("map_blocks", "map_rows")
                for st in self._stages
            )
        ):
            return None
        cache = frame_cache.active_cache(self._frame)
        devices = (
            cache.devices if cache is not None else device_pool.pool_devices()
        )
        if len(devices) < 2:
            return None
        layout, all_host = self._entry_layout()
        if not layout or not all_host:
            return None
        return devices, layout, cache

    def _pool_pads(self, sizes: List[int], layout) -> List[Optional[int]]:
        """Bucket targets for the pooled per-block chain (engine
        ``_bucket_plan`` analog), or all-None for exact shapes.

        Without padding an uneven frame compiles one chain executable
        per (block size, device); with it every block lands on one
        bucket signature per device.  Gating mirrors the engine: block
        bucketing enabled, no trim stage (padded rows must slice back,
        which needs row identity), and the WHOLE per-block chain proven
        row-independent by the jaxpr proof at the exact (real, padded)
        sizes — posed once on the composite ``_block_chain`` over the
        entry columns, so a cross-row ``map_blocks`` stage anywhere in
        the chain keeps exact shapes."""
        nb = len(sizes)
        none: List[Optional[int]] = [None] * nb
        if not bucketing.enabled() or any(st.trim for st in self._stages):
            return none
        targets = [
            bucketing.bucket_for(n) if n > 0 else None for n in sizes
        ]
        targets = [
            t if t is not None and t != sizes[i] else None
            for i, t in enumerate(targets)
        ]
        if all(t is None for t in targets):
            return none
        proof_sizes = tuple(
            sorted(
                {sizes[i] for i, t in enumerate(targets) if t is not None}
                | {t for t in targets if t is not None}
            )
        )
        sig = tuple(
            sorted(
                (n, tuple(np.shape(d)[1:]), str(np.dtype(dt)))
                for n, (d, dt) in layout.items()
            )
        )
        key = (proof_sizes, sig)
        if key not in self._pool_proofs:
            params_list = self._params_list()
            probe = Program(
                lambda **cols: self._block_chain(cols, params_list),
                sorted(layout),
            )
            specs = analysis.input_specs_for(probe, layout)
            try:
                ok = specs is not None and analysis.rows_independent(
                    probe, specs, proof_sizes
                )
            except analysis.AnalysisXCheckError:
                raise
            except Exception:
                ok = False
            self._pool_proofs[key] = ok
        return targets if self._pool_proofs[key] else none

    def _run_pooled(self, devices, layout, cache=None):
        """Map-terminal chain over the device pool: the fused per-block
        body (:meth:`_block_chain`) dispatches once per block on the
        block's assigned device, with per-device staging lanes and the
        bounded overlapped-readback window — the pipeline face of the
        engine's pooled placement (``ops/block_loop.py``).  Entry buffers
        are fresh host slices staged per block, so they donate exactly
        like the fused path's entry columns.

        ``cache`` (round 10, ``ops/frame_cache.py``): a sharded-cached
        entry frame runs AFFINITY dispatch instead — each block executes
        on the device already holding its shard, with no staging lanes,
        no donation (shards are shared state) and zero H2D for resident
        shards; evicted blocks and retry/quarantine recovery re-stage
        from the authoritative host columns.

        Donation-adoption: when sharding is on (entry cache present, or
        ``TFS_CACHE_SHARDED`` resolves devices), each block's OUTPUT
        buffers — already living on the block's execution device — are
        adopted as the cached shards of the result frame, so the next
        epoch of an iterative chain (``run`` feeding ``run``) starts
        sharded-cached and stages nothing.  The overlapped D2H readback
        still assembles the authoritative host columns; adopted shards
        are bytes-accounted against ``TFS_HBM_BUDGET``."""
        frame = self._frame
        with observability.verb_span(
            "pipeline", frame.num_rows, frame.num_blocks
        ) as span:
            donate = prefetch.donate_inputs() and cache is None
            if donate not in self._pool_compiled:
                self._pool_compiled[donate] = jax.jit(
                    lambda blk, params_list: self._block_chain(
                        blk, params_list
                    ),
                    **({"donate_argnums": (0,)} if donate else {}),
                )
            run = self._pool_compiled[donate]
            span.mark("validate")
            span.annotate("donate_entry", donate)
            sizes = frame.block_sizes
            nb = frame.num_blocks
            assignment = (
                list(cache.assignment)
                if cache is not None
                else device_pool.assign(sizes, len(devices))
            )
            pool = device_pool.PoolRun(
                devices, assignment, prefetch.prefetch_depth() or 1,
                affinity=cache is not None,
            )
            # block-level fault tolerance (ops/fault_tolerance.py): the
            # pooled per-block chain retries exactly like the eager map
            # verbs — re-staged entry buffers, quarantine redirects, by-
            # index reassembly.  None (the default) keeps this loop
            # byte-identical to the retry-free round-8 path.
            session = fault_tolerance.frame_session(
                nb, verb="pipeline", pool=pool
            )
            offsets = frame.offsets
            host_cols = {
                name: np.asarray(data) if not is_device_array(data) else data
                for name, (data, _) in layout.items()
            }

            pads = self._pool_pads(sizes, layout)

            def stage_block(bi, dev):
                lo, hi = offsets[bi], offsets[bi + 1]
                staged = {}
                for name, (data, dt) in layout.items():
                    a = host_cols[name][lo:hi]
                    if a.dtype != dt:
                        a = a.astype(dt)
                    if pads[bi] is not None:
                        a = bucketing.pad_rows(a, pads[bi])
                    observability.note_h2d_bytes(a.nbytes)
                    staged[name] = jax.device_put(a, dev)
                return staged

            def stage_cached(bi, dev_i):
                """Entry dict for one block of the sharded-cached frame:
                resident shard columns pass through on their device
                (bucket-padded device-side when needed); missing columns
                and evicted blocks re-stage from the host copy."""
                shard = (
                    cache.shard(bi) if dev_i == assignment[bi] else None
                )
                lo, hi = offsets[bi], offsets[bi + 1]
                staged = {}
                used = False
                for name, (data, dt) in layout.items():
                    v = shard.get(name) if shard is not None else None
                    if v is not None:
                        if pads[bi] is not None:
                            v = bucketing.pad_rows(v, pads[bi])
                        staged[name] = v
                        used = True
                        continue
                    a = host_cols[name][lo:hi]
                    if a.dtype != dt:
                        a = a.astype(dt)
                    if pads[bi] is not None:
                        a = bucketing.pad_rows(a, pads[bi])
                    observability.note_h2d_bytes(a.nbytes)
                    staged[name] = jax.device_put(a, devices[dev_i])
                return staged, used

            if cache is None:
                lanes = device_pool.lanes(devices, assignment, stage_block)
                lane_iters = [iter(l) for l in lanes]
                lane_dead = [False] * len(devices)
            else:
                lanes = []
            params_list = self._params_list()
            out_blocks: List[Optional[Dict[str, Any]]] = [None] * nb
            # donation-adoption: collect each block's device-resident
            # outputs when sharding is on (the result frame adopts them)
            adopt_outs = (
                [None] * nb
                if (
                    cache is not None
                    or len(frame_cache.shard_devices(None)) >= 2
                )
                else None
            )
            eff_assign: List[int] = []
            shard_hits = 0
            for bi in range(nb):
                cancellation.checkpoint()  # block boundary (pooled chain)
                di = assignment[bi]
                if cache is not None:
                    di_eff = pool.effective_device(di) if session else di
                    staged, used = (
                        stage_cached(bi, di_eff)
                        if (session is None or di_eff == di)
                        else (None, False)
                    )
                    if used:
                        shard_hits += 1
                        observability.note_cache_shard_hit()
                    elif session is not None and di_eff != di:
                        session.note_cache_restage()
                    if session is None:
                        outs = run(staged, params_list)
                        del staged
                    else:
                        holder = {"v": staged}
                        del staged

                        def attempt(a, dev_i, _bi=bi, _h=holder, _di=di):
                            # attempt 0 may consume the shard-backed
                            # entries; every retry (and any quarantine
                            # redirect) re-stages from the authoritative
                            # host columns on the CURRENT device
                            ins = (
                                _h.pop("v", None)
                                if (a == 0 and dev_i == _di)
                                else None
                            )
                            _h.clear()
                            if ins is None:
                                ins = stage_block(_bi, devices[dev_i])
                            return run(ins, params_list)

                        outs = session.run(
                            bi,
                            sizes[bi],
                            attempt,
                            device=lambda _di=di: pool.effective_device(
                                _di
                            ),
                        )
                        di_eff = pool.effective_device(di)
                elif session is None:
                    staged = next(lane_iters[di])
                    outs = run(staged, params_list)
                    del staged
                    di_eff = di
                else:
                    staged = block_loop.lane_next(
                        lane_iters[di], lane_dead, di, session, pool
                    )
                    holder = {"v": staged}
                    del staged

                    def attempt(a, dev_i, _bi=bi, _h=holder, _di=di):
                        # attempt 0 may consume the lane-staged entry
                        # buffers; every retry (and any quarantine
                        # redirect) re-stages fresh host slices — a
                        # donated-then-failed buffer is never re-used
                        ins = (
                            _h.pop("v", None)
                            if (a == 0 and dev_i == _di)
                            else None
                        )
                        _h.clear()
                        if ins is None:
                            ins = stage_block(_bi, devices[dev_i])
                        return run(ins, params_list)

                    outs = session.run(
                        bi,
                        sizes[bi],
                        attempt,
                        device=lambda _di=di: pool.effective_device(_di),
                    )
                    di_eff = pool.effective_device(di)
                if pads[bi] is not None:
                    # bucket-padded chain: slice the pad rows back off
                    # (the _pool_pads proof guarantees real rows' values)
                    outs = {k: v[: sizes[bi]] for k, v in outs.items()}
                if adopt_outs is not None:
                    adopt_outs[bi] = outs
                eff_assign.append(di_eff)
                pool.submit(bi, di_eff, sizes[bi], outs, out_blocks)
            pool.finish(out_blocks)
            span.annotate(
                "device_pool",
                pool.record(
                    sum(l.stats["stage_s"] for l in lanes),
                    sum(l.stats["wait_s"] for l in lanes),
                ),
            )
            if session is not None and session.events():
                span.annotate("fault_tolerance", session.record())
            span.mark("dispatch")
            out_frame = TensorFrame.from_blocks(out_blocks)
            # host-only / ragged source columns pass through unchanged when
            # the chain preserves row identity (no trim stage) — same rule
            # as the fused path.  Rebuild BEFORE adoption: the adopted
            # cache must ride the frame object actually returned.
            if not any(s.trim for s in self._stages):
                extra = [
                    c
                    for c in frame.columns
                    if c.info.name not in out_frame.column_names
                    and c.info.name not in self._visible
                ]
                if extra:
                    out_frame = TensorFrame(
                        list(out_frame.columns) + extra, out_frame.offsets
                    )
            adopted = (
                frame_cache.adopt(out_frame, devices, eff_assign, adopt_outs)
                if adopt_outs is not None
                else None
            )
            fc_rec: Dict[str, Any] = {}
            if cache is not None:
                fc_rec = cache.record()
                fc_rec["shard_hits"] = shard_hits
            if adopted is not None:
                fc_rec["adopted_blocks"] = adopted.resident_blocks()
                fc_rec["adopted_bytes_per_device"] = (
                    adopted.resident_bytes_per_device()
                )
            if fc_rec:
                span.annotate("frame_cache", fc_rec)
            return out_frame

    def _entry_layout(self) -> Tuple[Dict[str, Any], bool]:
        """``name -> (column data, effective entry dtype)`` plus whether
        every entry column is host-resident — the ONE walk behind both
        :meth:`_entry_cols` (which stages the data) and :meth:`warmup`
        (which builds matching specs), so a warmed executable's
        signature can never drift from the staged one.  Device-resident
        columns keep their own dtype (they are staged untouched;
        ``_body`` casts per block) and disable donation."""
        layout: Dict[str, Any] = {}
        all_host = True
        for name in self._needed_source_cols():
            c = self._frame.column(name)
            data = c.data
            if is_device_array(data):
                all_host = False
                dt = data.dtype
            else:
                dt = dtypes.coerce(c.info.scalar_type).np_dtype
            layout[name] = (data, dt)
        return layout, all_host

    def _entry_cols(self) -> Tuple[Dict[str, Any], bool]:
        """Source columns for the trace, staged onto the device.

        Host columns are cast then ``device_put`` back to back (async —
        the per-column transfers queue together on the link instead of
        being issued lazily by the jit call).  Returns ``(cols, donate)``:
        ``donate`` is True when every staged buffer is a fresh transfer
        this call created, so ``run``/``iterate`` may donate the entry
        arguments and the staged copies die with the dispatch (steady-
        state HBM holds one staged set).  Device-resident (cached)
        columns are shared frame state and disable donation; mesh
        placement keeps its own sharded path."""
        layout, all_host = self._entry_layout()
        cols = {}
        for name, (data, dt) in layout.items():
            if not is_device_array(data):
                data = np.asarray(data)
                if data.dtype != dt:
                    data = data.astype(dt)
            if self._mesh_mode:
                # rows land sharded over the engine's data axis; GSPMD
                # propagates from these input shardings through the trace
                data = self._engine._place_rows(jnp.asarray(data))
            cols[name] = data
        if self._mesh_mode or not cols:
            return cols, False
        return prefetch.stage_columns(cols), (
            all_host and prefetch.donate_inputs()
        )

    def warmup(self) -> "Pipeline":
        """AOT-lower and compile the fused ``run()``/``collect()``
        executable at the frame's entry signature without dispatching it
        — the pipeline face of the persistent-executable-cache cold
        start (``TFS_COMPILE_CACHE`` / ``Program.aot_compile``).

        With the cache configured, a fresh serving process calls
        ``pipe.warmup()`` before traffic arrives and the fused
        executable deserializes from disk instead of running XLA; the
        subsequent ``run()`` re-traces (cheap) and fetches the same
        backend artifact.  NOT covered: ``iterate()`` compiles a
        different executable (the chain scanned over steps) — its first
        call in a cached process still fetches from disk *if a previous
        process ran the same iterate*, but this method does not prime
        it.  Single-process / mesh-less chains only: a mesh-global
        chain's executable depends on the live sharding, which staging
        establishes."""
        if not self._stages:
            raise ValidationError("pipeline.warmup: empty pipeline")
        if self._mesh_mode:
            raise ValidationError(
                "pipeline.warmup: mesh-global chains compile against live "
                "shardings; warm them by running once."
            )
        layout, all_host = self._entry_layout()
        donate = bool(layout) and all_host and prefetch.donate_inputs()
        specs = {
            name: jax.ShapeDtypeStruct(tuple(np.shape(data)), dt)
            for name, (data, dt) in layout.items()
        }
        if donate not in self._compiled:
            self._compiled[donate] = jax.jit(
                lambda cols, params_list: self._body(cols, params_list),
                **({"donate_argnums": (0,)} if donate else {}),
            )
        param_specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
            self._params_list(),
        )
        with observability.suppress_trace_count():
            self._compiled[donate].lower(specs, param_specs).compile()
        return self

    def collect(self):
        """``run()`` + host materialisation (the one sync)."""
        out = self.run()
        if self._row_stage:
            host = jax.device_get(out)
            return {k: np.asarray(v) for k, v in host.items()}
        return out.uncache()

    def iterate(
        self,
        num_steps: int,
        carry: Mapping[str, str],
        collect: Sequence[str] = (),
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Run the chain ``num_steps`` times in ONE dispatch (``lax.scan``),
        feeding outputs back into stage params between steps.

        ``carry``: output name -> param name.  After each step, the named
        output becomes the new value of every stage param with that name —
        the on-device form of the ``update_params`` iterative-driver
        contract (the reference re-broadcasts a re-built graph per step,
        ``kmeans_demo.py:68-80``; the eager engine updates params per
        dispatch; here the update never leaves the device).

        ``collect``: output names whose per-step values are stacked and
        returned as history (e.g. the loss curve).

        Returns ``(final_params, history)`` — ``final_params`` maps each
        carried param name to its final device value (the stage programs are
        also updated in place, so ``run()``/``iterate()`` continue from the
        new state); ``history`` maps each collected name to a ``[num_steps,
        ...]`` device array."""
        if not self._row_stage:
            raise ValidationError(
                "pipeline.iterate: requires a row-terminal chain "
                "(reduce/then) so step outputs can feed back into params."
            )
        if not carry:
            raise ValidationError(
                "pipeline.iterate: carry={} would loop without feedback; "
                "use run() in a host loop instead."
            )
        targets: List[Tuple[int, str, str]] = []  # (stage idx, param, output)
        for out_name, param_name in carry.items():
            hits = [
                i
                for i, st in enumerate(self._stages)
                if st.program is not None
                and param_name in st.program.param_names
            ]
            if not hits:
                raise ValidationError(
                    f"pipeline.iterate: carry target param {param_name!r} "
                    f"does not exist on any stage program."
                )
            for i in hits:
                targets.append((i, param_name, out_name))

        cols, donate = self._entry_cols()
        key = (num_steps, tuple(sorted(carry.items())), tuple(collect), donate)
        if key not in self._iter_compiled:

            def loop(cols, params_list):
                def step(pl, _):
                    row = self._body(cols, pl)
                    for name in list(carry) + list(collect):
                        if name not in row:
                            raise ValidationError(
                                f"pipeline.iterate: {name!r} is not an "
                                f"output of the chain; outputs are "
                                f"{sorted(row)}."
                            )
                    new_pl = [dict(p) for p in pl]
                    for i, pname, oname in targets:
                        old = new_pl[i][pname]
                        new = row[oname]
                        if not hasattr(old, "shape"):
                            raise ValidationError(
                                f"pipeline.iterate: param {pname!r} is a "
                                f"pytree, not a single array; only "
                                f"leaf-array params can be carried — bind "
                                f"the leaves as separate params."
                            )
                        if new.shape != old.shape:
                            raise ValidationError(
                                f"pipeline.iterate: carried output "
                                f"{oname!r} has shape {new.shape} but param "
                                f"{pname!r} has shape {old.shape}; shapes "
                                f"must match for a stable loop carry."
                            )
                        new_pl[i][pname] = new.astype(old.dtype)
                    return new_pl, {k: row[k] for k in collect}

                final_pl, hist = jax.lax.scan(
                    step, params_list, None, length=num_steps
                )
                finals = {}
                for i, pname, _ in targets:
                    finals[pname] = final_pl[i][pname]
                return finals, hist

            self._iter_compiled[key] = jax.jit(
                loop, **({"donate_argnums": (0,)} if donate else {})
            )

        with observability.verb_span(
            "pipeline.iterate", self._frame.num_rows, self._frame.num_blocks
        ) as span:
            span.mark("validate")
            span.annotate("donate_entry", donate)
            finals, hist = self._iter_compiled[key](cols, self._params_list())
            del cols
            span.mark("dispatch")
            # resume contract: stage programs pick up the final params
            for i, pname, _ in targets:
                self._stages[i].program.update_params(**{pname: finals[pname]})
            return finals, hist


def pipeline(frame: TensorFrame, engine=None) -> Pipeline:
    """Start a fused verb chain over ``frame`` (see :class:`Pipeline`).

    ``engine``: pass a ``parallel.MeshExecutor`` to run the chain
    mesh-global — source columns sharded over its data axis, reduce
    combines on ICI (module docstring)."""
    if getattr(frame, "_tfs_lazy", False):
        # explicit Pipeline over a lazy frame: materialise the plan
        # first — a Pipeline is its own fusion surface
        from . import planner

        frame = planner.ensure_frame(frame)
    if (
        engine is not None
        and hasattr(engine, "mesh")
        and getattr(engine, "mode", "global") != "global"
    ):
        raise ValidationError(
            "pipeline: a fused chain has exactly one logical block, so "
            "only mode='global' MeshExecutors compose with it; per-block "
            "(partition) semantics need the eager MeshExecutor verbs."
        )
    return Pipeline(frame, engine=engine)
