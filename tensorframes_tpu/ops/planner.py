"""Lazy verb-graph planner: fuse, prune, auto-cache (``TFS_PLAN``, round 14).

The reference exposes a *logical plan* surface — ``explain``/``analyze``
describe what will run before anything does (PAPER.md §L3) — but every
verb in this port executed eagerly until this round: a chained
``map -> map -> map`` pays one dispatch per verb, under the device pool
each link re-stages the previous verb's host-assembled output, and a
twice-consumed intermediate (the kmeans-epochs shape) re-stages per
consumer unless the user remembers ``cache(sharded=True)``.

``frame.lazy()`` (or ``TFS_PLAN=1`` for the module-level verbs) switches
a frame into *planned* mode: map verbs append :class:`PlanStep`\\ s to a
logical plan instead of dispatching, and the plan is optimized and
executed on first materialisation (``collect``/``to_arrays``/…, a
reduce verb, or ``aggregate``).  The optimizer:

* **fuses** maximal runs of adjacent map stages into ONE chained
  dispatch: each block is staged once (pruned), the stages' OWN
  compiled entries (``Program.jitted``/``vmapped`` — the exact
  executables the eager verbs run, bucket plans and persistent compile
  cache included) apply back-to-back on the block's device, and one
  readback returns the chain's outputs.  Under the pool this removes
  the per-verb host-assembly + re-staging round trip entirely; on the
  serial path intermediates stay device-resident.  Deliberately NOT a
  single XLA trace of the whole chain: XLA contracts arithmetic across
  stage boundaries (a stage-1 ``mul`` feeding a stage-2 ``add`` becomes
  one fma), which would round differently from the eager per-verb
  dispatches — per-stage executables make the six-verb bit-identity
  invariant structural instead of numerical luck;
* **prunes dead columns before staging**: the chain stages exactly the
  source columns some stage consumes, so columns no stage reads are
  never ``device_put`` (``h2d_bytes_staged`` drops measurably).  For
  non-trimmed chains the pruned columns still ride into the output
  frame as untouched host passthroughs — same values, zero transfer;
* **auto-inserts a sharded cache** when a subplan has >= 2 consumers
  (two derived chains, or repeated terminal consumption — epochs):
  pooled chain outputs are donation-ADOPTED as the result's shards
  (``frame_cache.adopt``), and re-consumed intermediates get
  ``cache(sharded=True)``-style placement over exactly the columns
  downstream stages read.  Either way a ``weakref.finalize`` releases
  the shards (refunding ``TFS_HBM_BUDGET``) when the planned frame is
  garbage-collected;
* **chooses pool vs fused-serial per fused group** from the existing
  roofline cost model (``roofline._aggregate_cost`` over the composed
  chain's compiled HLO → flops/byte) and the retrace state (a plan
  whose stage executables are already warm pools for free; a cold,
  transfer-bound chain stays serial — device-resident chaining, no
  per-device compiles).  The decision — and why — is recorded in the
  ``plan`` span annotation and rendered by ``tfs.explain``.

Eager execution stays the default (``TFS_PLAN`` unset / ``0``); every
planned verb is bit-identical to its eager counterpart, including the
pooled, sharded-cache, and fault-injection legs
(``tests/test_planner.py``).  Column ORDER of a planned map-terminal
output may differ from the eager chain's (derived outputs sort together
before source passthroughs); names and values are identical.

Round 19 promotes the planner into the **system-wide optimizer**
(ISSUE 14).  Four legs on top of the round-14 chain optimizer:

* **fused terminal reduce/aggregate** — a plan ending in
  ``reduce_rows``/``reduce_blocks`` folds each block's partial INSIDE
  the pooled chain dispatch, on the block's device, reusing the
  engine's own ``_reduce_*_setup`` executables and finishing with the
  engine's ``_combine_partials`` (stack in block order, re-apply once)
  — the EXACT fold shape of the eager verbs, so bit-identity is
  structural.  The materialized intermediate frame is eliminated
  entirely: no per-block D2H assembly, no re-staging H2D for the
  reduce.  A terminal ``aggregate`` (via a deferred
  :class:`LazyGroupedFrame`) prunes the chain's fetches to exactly the
  key + reduced columns before the one materialisation it still needs
  (group structure is data-dependent), then runs the UNCHANGED eager
  aggregate so grouping numerics cannot drift.
* **cross-plan common-subexpression sharing** — a process-wide
  plan-signature registry (source frame + step programs + live param
  identity, weakref-guarded) lets concurrent bridge requests and
  separate ``.lazy()`` chains with an identical subplan execute it
  ONCE: the owner runs under a private root ledger and every consumer
  registered by completion absorbs an exact integer share
  (:meth:`observability.RequestLedger.absorb`, the coalescer's
  attribution contract), so per-request ledgers still sum to the
  global counters delta bit for bit.  Later identical chains reuse the
  shared (auto-cached) result while it is alive (``plan_cse_hits``).
* **pipelined multi-epoch** :func:`iterate_epochs` — the planner-aware
  epoch driver: the entry frame's sharded cache is inserted on the
  FIRST consumption (the loop declares its >= 2 consumptions up
  front), evicted shards are re-staged through a background primer
  between epochs so epoch N+1's blocks are resident while epoch N's
  host work runs, and steady-state epochs stage 0 H2D bytes and
  re-trace nothing.
* **plans over streaming verbs** — stacked per-window map stages
  (``StreamFrame.map_blocks``/``map_rows`` chains and the relational
  pipeline's map stages) route through :func:`run_window_chain`:
  fusion, dead-column pruning, and the static
  ``analysis.rows_independent`` bucket pads apply per window
  (``plan_stream_windows``).  With ``TFS_PLAN_CALIBRATE`` on, the
  measured rows/s every plan execution records (the substance behind
  ``explain(analyze=True)``) feeds back into the pool-vs-serial
  decision: once both dispatches have been measured for a chain
  signature, the faster one wins over the static intensity threshold.

Knobs:

* ``TFS_PLAN`` — ``1``/``true`` routes the module-level verbs through
  the planner for plain frames; ``frame.lazy()`` opts in per frame
  regardless of the env.
* ``TFS_PLAN_POOL_MIN_INTENSITY`` — flops/byte below which a COLD fused
  group prefers the serial fused dispatch over the device pool (default
  ``1.0``; warm executables always pool when the pool is available).
* ``TFS_PLAN_CSE`` — cross-plan common-subexpression sharing (default
  on for planned executions; ``0`` disables the registry).
* ``TFS_PLAN_CALIBRATE`` — measured-throughput feedback into the
  pool-vs-serial decision (default off; ``1`` prefers whichever
  dispatch measured faster for the chain signature).
"""

from __future__ import annotations

import collections
import logging
import threading
import time
import weakref
from typing import Any, Dict, List, Mapping, Optional, Sequence, Set, Tuple

import numpy as np

from .. import cancellation, dtypes, observability
from .. import envutil
from .. import roofline as _roofline
from ..frame import TensorFrame
from ..program import Program
from ..schema import ColumnInfo, Schema
from . import (
    block_loop,
    bucketing,
    device_pool,
    fault_tolerance,
    frame_cache,
    prefetch,
)
from ..analysis import rowdep as analysis
from .engine import (
    _DEFAULT,
    Executor,
    GroupedFrame,
    _check_shape_hints,
    _np,
)
from .pipeline import analyzed_outputs
from .validation import ValidationError

_log = logging.getLogger("tensorframes_tpu.planner")

ENV_PLAN = "TFS_PLAN"
ENV_POOL_INTENSITY = "TFS_PLAN_POOL_MIN_INTENSITY"
ENV_CSE = "TFS_PLAN_CSE"
ENV_CALIBRATE = "TFS_PLAN_CALIBRATE"
_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def planning_enabled() -> bool:
    """Whether ``TFS_PLAN`` routes the module-level verbs through the
    planner for plain frames (read per call: bench legs and tests flip
    it mid-process)."""
    return envutil.env_raw(ENV_PLAN).lower() in _TRUTHY


def cse_enabled() -> bool:
    """Cross-plan common-subexpression sharing (``TFS_PLAN_CSE``): on
    by default for planned executions, ``0`` disables the registry."""
    return envutil.env_raw(ENV_CSE).lower() not in _FALSY


def calibrate_enabled() -> bool:
    """Measured-throughput feedback into the pool-vs-serial decision
    (``TFS_PLAN_CALIBRATE``, default off)."""
    return envutil.env_raw(ENV_CALIBRATE).lower() in _TRUTHY


def pool_min_intensity() -> float:
    raw = envutil.env_raw(ENV_POOL_INTENSITY)
    if not raw:
        return 1.0
    try:
        return float(raw)
    except ValueError:
        return 1.0


class _SerialExecutor(Executor):
    """The fused-serial dispatch target: the exact default engine with
    the device-pool scheduler opted out — the planner's per-group
    "serial" decision, expressed the same way ``MeshExecutor`` opts out
    (``supports_device_pool``) so no dispatch-loop code forks."""

    supports_device_pool = False


_SERIAL = _SerialExecutor()


# ---------------------------------------------------------------------------
# plan steps + fusion metadata
# ---------------------------------------------------------------------------


class PlanStep:
    """One recorded map verb (reduce/aggregate are materialisation
    points, not steps)."""

    __slots__ = ("kind", "program", "trim", "host_stage")

    def __init__(
        self,
        kind: str,
        program: Program,
        trim: bool = False,
        host_stage: Optional[Mapping[str, Any]] = None,
    ):
        self.kind = kind  # "map_blocks" | "map_rows"
        self.program = program
        self.trim = trim
        self.host_stage = host_stage

    @property
    def label(self) -> str:
        if self.kind == "map_blocks" and self.trim:
            return "map_blocks_trimmed"
        return self.kind

    @property
    def stage_bound(self) -> bool:
        """Whether this step must run eagerly because it carries host
        preprocessing (explicit ``host_stage`` or an importer
        ``host_prelude``) — host fns cannot join a fused chain."""
        return bool(self.host_stage) or bool(
            getattr(self.program, "host_prelude", None)
        )


def _device_infos(frame: TensorFrame) -> Dict[str, ColumnInfo]:
    """Device-feedable uniform columns of a concrete frame — the
    columns a fused chain may consume."""
    out: Dict[str, ColumnInfo] = {}
    for c in frame.columns:
        if c.info.scalar_type.device_ok and not c.is_ragged:
            out[c.info.name] = c.info
    return out


# Per-stage shape inference is an eval_shape trace (~ms): an epochs loop
# rebuilding the same chain would pay it per stage per epoch, which is
# pure overhead on a hot path that dispatches in single-digit ms.  Keyed
# by program identity + the exact input info signature, weakref-guarded
# like the fusion cache.
_ANALYSIS_CACHE: "collections.OrderedDict[Any, Tuple[Any, Dict]]" = (
    collections.OrderedDict()
)
_ANALYSIS_CACHE_CAP = 256


def _analyzed_outputs_cached(
    program: Program, infos: Mapping[str, ColumnInfo], cell: bool
) -> Dict[str, ColumnInfo]:
    key = (
        id(program),
        cell,
        tuple(
            sorted(
                (n, ci.scalar_type.name, tuple(ci.block_shape))
                for n, ci in infos.items()
            )
        ),
    )
    hit = _ANALYSIS_CACHE.get(key)
    if hit is not None:
        ref, outs = hit
        if ref() is program:
            _ANALYSIS_CACHE.move_to_end(key)
            return outs
        del _ANALYSIS_CACHE[key]
    outs = analyzed_outputs(program, infos, cell=cell, verb="plan")
    _ANALYSIS_CACHE[key] = (weakref.ref(program), outs)
    while len(_ANALYSIS_CACHE) > _ANALYSIS_CACHE_CAP:
        _ANALYSIS_CACHE.popitem(last=False)
    return outs


def _fusable_run(
    steps: Sequence[PlanStep], visible: Dict[str, ColumnInfo]
) -> Tuple[int, Optional[str], Dict[str, ColumnInfo]]:
    """Length of the maximal fusable prefix of ``steps`` given the
    ``visible`` device-feedable columns at entry, the reason the run
    stopped (None when it covered every step), and the visible columns
    AFTER the prefix (so callers can keep walking a chain).

    A step fuses when: no host stage, every input resolves to a visible
    device-feedable uniform column, and shape inference succeeds."""
    visible = dict(visible)
    n = 0
    why = None
    for st in steps:
        if st.stage_bound:
            why = "host_stage"
            break
        infos: Dict[str, ColumnInfo] = {}
        bad = None
        for name in st.program.input_names:
            col = st.program.column_for_input(name)
            ci = visible.get(col)
            if ci is None:
                bad = col
                break
            infos[name] = ci
        if bad is not None:
            why = f"column {bad!r} is host-only/ragged or absent"
            break
        try:
            outs = _analyzed_outputs_cached(
                st.program, infos, cell=st.kind == "map_rows"
            )
        except Exception as e:  # analysis failure: run the stage eagerly
            why = f"shape inference failed ({type(e).__name__})"
            break
        if st.trim:
            visible = dict(outs)
        else:
            visible.update(outs)
        n += 1
    return n, why, visible


class _FusedMeta:
    """One fused group's compile-time facts: the chain's staged entry
    columns (pruned), final fetches, per-stage bucket-proof specs,
    per-stage liveness (columns still needed after each stage — the
    donation/free analysis), and the composed ANALYSIS program the
    roofline decision probes (never executed — execution applies the
    stage programs' own entries)."""

    __slots__ = (
        "program",
        "fetches",
        "src_inputs",
        "pruned",
        "trim",
        "steps",
        "param_slots",
        "stage_specs",
        "stage_infos",
        "final_infos",
        "live_after",
        # round 20: memoized calibration fingerprints per frame shape
        "_calib_fps",
    )


# Fusion metadata is cached process-wide so re-running a rebuilt chain
# (same stage Programs, same entry layout) skips re-analysis and reuses
# one probe program.  Keys hold id()s; entries carry weakrefs so a
# recycled id can never alias stale metadata onto different programs.
_FUSED_CACHE: "collections.OrderedDict[Any, Tuple[Any, _FusedMeta]]" = (
    collections.OrderedDict()
)
_FUSED_CACHE_CAP = 64


def _entry_signature(frame: TensorFrame) -> Tuple:
    sig = []
    for c in frame.columns:
        if c.info.scalar_type.device_ok and not c.is_ragged:
            sig.append(
                (c.info.name, tuple(c.data.shape[1:]), str(c.data.dtype))
            )
    return tuple(sorted(sig))


def _compose(
    steps: Sequence[PlanStep],
    frame: TensorFrame,
    keep: Optional[Set[str]] = None,
) -> _FusedMeta:
    """Analyse ``steps`` as one fused chain over ``frame``'s entry
    columns (cached): which source columns the chain consumes (its
    pruned staging set), what it produces, the per-stage specs the
    bucket-padding proof needs, and a composed probe Program whose
    compiled HLO feeds the pool/serial cost decision.

    ``keep`` (round 19, terminal fetch pruning): restrict the chain's
    fetches to the derived columns a terminal consumer actually reads —
    a reduce's base columns, an aggregate's keys + bases — so liveness
    can free/donate every other intermediate and nothing unread is ever
    assembled back to host."""
    key = (
        tuple((st.kind, id(st.program), st.trim) for st in steps),
        _entry_signature(frame),
        None if keep is None else tuple(sorted(keep)),
    )
    hit = _FUSED_CACHE.get(key)
    if hit is not None:
        refs, meta = hit
        if all(r() is st.program for r, st in zip(refs, steps)):
            _FUSED_CACHE.move_to_end(key)
            _sync_probe_params(meta)
            return meta
        del _FUSED_CACHE[key]

    import jax

    src_infos = _device_infos(frame)
    origin: Dict[str, str] = {n: "source" for n in src_infos}
    infos_now: Dict[str, ColumnInfo] = dict(src_infos)
    src_inputs: List[str] = []
    param_slots: List[Tuple[str, Program]] = []  # (param name, owner)
    stage_specs: List[Optional[Dict[str, Any]]] = []
    stage_infos: List[Dict[str, ColumnInfo]] = []
    for st in steps:
        step_infos: Dict[str, ColumnInfo] = {}
        for name in st.program.input_names:
            col = st.program.column_for_input(name)
            if col not in origin:
                raise ValidationError(
                    f"plan.{st.label}: program input {name!r} requests "
                    f"column {col!r}, which is not available at this "
                    f"point in the chain. Available: {sorted(origin)}."
                )
            if origin[col] == "source" and col not in src_inputs:
                src_inputs.append(col)
            step_infos[name] = infos_now[col]
        # (2, *cell) probe specs for the row-independence proof behind
        # bucket padding — None when a cell dim is Unknown at this stage
        stage_specs.append(
            analysis.input_specs_for(st.program, step_infos)
        )
        stage_infos.append(dict(step_infos))
        outs = _analyzed_outputs_cached(
            st.program, step_infos, cell=st.kind == "map_rows"
        )
        if st.trim:
            origin = {n: "derived" for n in outs}
            infos_now = dict(outs)
        else:
            origin.update({n: "derived" for n in outs})
            infos_now.update(outs)
        for p in st.program.param_names:
            if all(p != q for q, _ in param_slots):
                param_slots.append((p, st.program))
    fetches = sorted(n for n, kind in origin.items() if kind == "derived")
    if keep is not None:
        fetches = [f for f in fetches if f in keep]
    if not fetches:
        raise ValidationError(
            "plan: the fused chain produces no derived outputs"
            + (" the terminal consumer reads" if keep is not None else "")
        )
    pruned = sorted(set(src_infos) - set(src_inputs))
    trim = any(st.trim for st in steps)

    steps_t = tuple(steps)
    stage_params = tuple(tuple(st.program.param_names) for st in steps_t)

    def probe(**kw):
        # ANALYSIS-ONLY composed body (roofline cost probe): the real
        # execution applies each stage's own compiled entry so fused
        # rounding is bit-identical to eager (see module docstring)
        import jax as _jax

        blk: Dict[str, Any] = {c: kw[c] for c in src_inputs}
        for st, pnames in zip(steps_t, stage_params):
            prog = st.program
            params = {p: kw[p] for p in pnames}
            inputs = {
                n: blk[prog.column_for_input(n)] for n in prog.input_names
            }
            if st.kind == "map_rows":
                outs = _jax.vmap(
                    lambda ins, _p=params, _pr=prog: _pr.call(ins, _p),
                    in_axes=(0,),
                )(inputs)
            else:
                outs = prog.call(inputs, params)
            blk = dict(outs) if st.trim else {**blk, **outs}
        return {f: blk[f] for f in fetches}

    merged_params = {p: owner._params[p] for p, owner in param_slots}
    program = Program(
        probe,
        list(src_inputs) + [p for p, _ in param_slots],
        fetches=fetches,
        params=merged_params,
    )

    # liveness: columns still needed AFTER stage k (later stages'
    # inputs + the final fetches) — drives both the dead-buffer frees
    # between stages and the donation eligibility below
    live = set(fetches)
    live_after: List[Set[str]] = [set() for _ in steps_t]
    for k in range(len(steps_t) - 1, -1, -1):
        live_after[k] = set(live)
        live |= {
            steps_t[k].program.column_for_input(n)
            for n in steps_t[k].program.input_names
        }

    meta = _FusedMeta()
    meta.program = program
    meta.fetches = fetches
    meta.src_inputs = list(src_inputs)
    meta.pruned = pruned
    meta.trim = trim
    meta.steps = steps_t
    meta.param_slots = tuple(param_slots)
    meta.stage_specs = stage_specs
    meta.stage_infos = stage_infos
    meta.final_infos = dict(infos_now)
    meta.live_after = live_after
    refs = tuple(weakref.ref(st.program) for st in steps_t)
    _FUSED_CACHE[key] = (refs, meta)
    while len(_FUSED_CACHE) > _FUSED_CACHE_CAP:
        _FUSED_CACHE.popitem(last=False)
    return meta


def _sync_probe_params(meta: _FusedMeta) -> None:
    """Keep the probe program's params tracking the live stage params
    (shape-stable by ``update_params``' contract), so its cost analysis
    and cached specs never go stale.  Execution always reads the stage
    programs' own live params via their compiled entries."""
    for p, owner in meta.param_slots:
        live = owner._params.get(p)
        if live is not None and meta.program._params.get(p) is not live:
            meta.program._params[p] = live


# ---------------------------------------------------------------------------
# measured-throughput calibration (TFS_PLAN_CALIBRATE, round 19)
# ---------------------------------------------------------------------------
#
# Every plan execution already measures itself (`_measured`, the
# substance behind ``explain(analyze=True)``).  With the knob on those
# measurements feed BACK into the pool-vs-serial decision: per chain
# signature the best observed rows/s per dispatch kind is kept, and once
# both kinds have been measured the faster one wins over the static
# ``TFS_PLAN_POOL_MIN_INTENSITY`` threshold — the calibration loop for
# real TPU hosts where H2D is PCIe rather than memcpy and the roofline's
# flops/byte alone misjudges the crossover.

_CALIBRATION: "collections.OrderedDict[Any, Dict[str, float]]" = (
    collections.OrderedDict()
)
_CALIBRATION_CAP = 256
_CALIBRATION_LOCK = threading.Lock()

# -- cross-process persistence (round 20) ------------------------------------
#
# The in-memory table keys on live object ids — exact, but dead with the
# process, so every restarted replica re-learned pool-vs-serial from
# cold heuristics (the round-19 open item).  With BOTH knobs on
# (TFS_PLAN_CALIBRATE + TFS_COMPILE_CACHE) measurements also persist to
# ``<compile-cache>/tfs_calibration-v1.json`` under a STABLE chain
# fingerprint (step kinds/trims + program input/fetch/feed names +
# entry signature + fetches + rows + blocks — no ids), versioned and
# atomically replaced.  Lookup order: live in-memory entry first (object
# identity is stricter), persisted fingerprint second — so a fresh
# process's FIRST request picks the measured winner instead of the
# static intensity threshold.  A fingerprint collision can only steer a
# heuristic (decision quality), never correctness: every dispatch kind
# is bit-identical by contract.

_CALIB_PERSIST_FORMAT = "tfs-calibration-v1"
_calib_persist: Optional[Dict[str, Dict[str, float]]] = None
_calib_persist_dir: Optional[str] = None


def _calib_persist_path(cache_dir: str) -> str:
    import os

    return os.path.join(cache_dir, f"{_CALIB_PERSIST_FORMAT}.json")


def _calib_persist_table() -> Optional[Dict[str, Dict[str, float]]]:
    """The persisted fingerprint table (lock held by caller), lazily
    loaded from the active compile-cache dir; None when no persistent
    home is configured."""
    global _calib_persist, _calib_persist_dir
    from .. import compile_cache

    d = compile_cache.cache_dir()
    if not d:
        return None
    if _calib_persist is not None and _calib_persist_dir == d:
        return _calib_persist
    import json

    table: Dict[str, Dict[str, float]] = {}
    try:
        with open(_calib_persist_path(d), "rb") as f:
            doc = json.loads(f.read().decode())
        if (
            isinstance(doc, dict)
            and doc.get("format") == _CALIB_PERSIST_FORMAT
        ):
            for fp, rec in (doc.get("entries") or {}).items():
                table[str(fp)] = {
                    k: float(v)
                    for k, v in rec.items()
                    if k in ("pool", "serial")
                }
    except (OSError, ValueError):
        pass  # absent / torn / old format: start fresh
    _calib_persist = table
    _calib_persist_dir = d
    return table


def _calib_persist_save() -> None:
    """Atomic-replace write of the persisted table (lock held by
    caller).  The file is tiny (<= _CALIBRATION_CAP entries) — a write
    per measured execution is noise next to the execution itself."""
    import json
    import os

    if _calib_persist is None or not _calib_persist_dir:
        return
    # bound like the in-memory table: drop oldest-inserted overflow
    while len(_calib_persist) > _CALIBRATION_CAP:
        _calib_persist.pop(next(iter(_calib_persist)))
    path = _calib_persist_path(_calib_persist_dir)
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(
                json.dumps(
                    {
                        "format": _CALIB_PERSIST_FORMAT,
                        "entries": _calib_persist,
                    }
                ).encode()
            )
        os.replace(tmp, path)
    except OSError:
        _log.warning(
            "planner: calibration persistence write failed", exc_info=True
        )


def _calib_fingerprint(meta: "_FusedMeta", frame: TensorFrame) -> str:
    """A stable, cross-process fingerprint of the calibration workload:
    everything ``_calib_key`` captures EXCEPT object identity.
    Memoized on the meta (keyed by the frame-shape half) — the JSON +
    sha256 walk must not run per planned dispatch."""
    import hashlib
    import json

    memo_key = (frame.num_rows, frame.num_blocks, _entry_signature(frame))
    memo = getattr(meta, "_calib_fps", None)
    if memo is None:
        memo = meta._calib_fps = {}
    hit = memo.get(memo_key)
    if hit is not None:
        return hit

    doc = {
        "steps": [
            {
                "kind": st.kind,
                "trim": bool(st.trim),
                "inputs": list(st.program._input_names),
                "fetches": st.program._declared_fetches or [],
                "feed": sorted(st.program._feed.items()),
            }
            for st in meta.steps
        ],
        "entry": _entry_signature(frame),
        "fetches": list(meta.fetches),
        "rows": frame.num_rows,
        "blocks": frame.num_blocks,
    }
    fp = hashlib.sha256(
        json.dumps(doc, sort_keys=True, default=str).encode()
    ).hexdigest()[:24]
    if len(memo) < 64:
        memo[memo_key] = fp
    return fp


def _calib_key(meta: "_FusedMeta", frame: TensorFrame) -> Tuple:
    # fetches distinguish a keep-pruned terminal chain from the full
    # chain of the same steps — their D2H volumes (and so their
    # measured rows/s) are different workloads — and the frame SIZE is
    # part of the workload too: the pool/serial crossover moves with
    # rows and block count, so a small frame's serial win must never
    # decide a large frame's dispatch
    return (
        tuple((st.kind, id(st.program), st.trim) for st in meta.steps),
        _entry_signature(frame),
        tuple(meta.fetches),
        frame.num_rows,
        frame.num_blocks,
    )


def _calib_entry(key: Tuple, meta: "_FusedMeta") -> Optional[Dict]:
    """The live entry for a chain (lock held by caller).  Keys embed
    ``id()``s, so — like ``_FUSED_CACHE`` — each record carries weakrefs
    to its programs and a recycled id can never alias a dead chain's
    measurements onto a different one (a stale entry is dropped)."""
    rec = _CALIBRATION.get(key)
    if rec is None:
        return None
    if not all(
        r() is st.program for r, st in zip(rec["_refs"], meta.steps)
    ):
        del _CALIBRATION[key]
        return None
    return rec


def _calib_note(
    meta: "_FusedMeta", frame: TensorFrame, dispatch: str, rows_per_s
) -> None:
    """Record one measured pool/serial execution.  ``affinity``
    dispatches (resident shards, ~0 H2D) are NOT folded into the pool
    bucket — their throughput would inflate the pool estimate used to
    decide uncached dispatches — and CSE reuses measure nothing."""
    if rows_per_s is None or dispatch not in ("pool", "serial"):
        return
    key = _calib_key(meta, frame)
    with _CALIBRATION_LOCK:
        rec = _calib_entry(key, meta)
        if rec is None:
            rec = _CALIBRATION[key] = {
                "_refs": tuple(
                    weakref.ref(st.program) for st in meta.steps
                ),
            }
        rec[dispatch] = max(rec.get(dispatch, 0.0), float(rows_per_s))
        _CALIBRATION.move_to_end(key)
        while len(_CALIBRATION) > _CALIBRATION_CAP:
            _CALIBRATION.popitem(last=False)
        # cross-process persistence (compile-cache dir configured):
        # fold the measurement into the fingerprint table too, so a
        # restarted process starts from measured history
        persisted = _calib_persist_table()
        if persisted is not None:
            fp = _calib_fingerprint(meta, frame)
            prec = persisted.setdefault(fp, {})
            if float(rows_per_s) > prec.get(dispatch, 0.0):
                # write the (tiny) file only when the best measurement
                # actually moved — steady state pays zero file writes
                prec[dispatch] = float(rows_per_s)
                _calib_persist_save()


def _calib_lookup(
    meta: "_FusedMeta", frame: TensorFrame
) -> Optional[Dict[str, float]]:
    key = _calib_key(meta, frame)
    with _CALIBRATION_LOCK:
        rec = _calib_entry(key, meta)
        live = (
            {k: v for k, v in rec.items() if not k.startswith("_")}
            if rec is not None
            else {}
        )
        persisted = _calib_persist_table()
        if persisted is not None:
            # persisted history fills what this process has not yet
            # measured (the post-restart first request); a live
            # measurement of the same kind wins — it is the fresher
            # observation of THIS process's conditions
            for k, v in persisted.get(
                _calib_fingerprint(meta, frame), {}
            ).items():
                live.setdefault(k, float(v))
        return live or None


def reset_calibration(persisted: bool = False) -> None:
    """Clear the in-memory calibration table (tests/bench legs);
    ``persisted=True`` also forgets the loaded fingerprint table so the
    next lookup re-reads the compile-cache file from disk."""
    global _calib_persist, _calib_persist_dir
    with _CALIBRATION_LOCK:
        _CALIBRATION.clear()
        if persisted:
            _calib_persist = None
            _calib_persist_dir = None


def calibration_snapshot() -> List[Dict[str, Any]]:
    """The live calibration table (test/bench surface): one record per
    measured chain signature with the best rows/s per dispatch kind."""
    with _CALIBRATION_LOCK:
        return [
            {
                "stages": len(k[0]),
                **{
                    kk: vv
                    for kk, vv in v.items()
                    if not kk.startswith("_")
                },
            }
            for k, v in _CALIBRATION.items()
        ]


# ---------------------------------------------------------------------------
# pool-vs-serial decision (roofline + retrace state)
# ---------------------------------------------------------------------------


def _fused_intensity(
    program: Program, frame: TensorFrame
) -> Optional[float]:
    """Arithmetic intensity (flops/byte) of the fused chain at this
    frame's largest (bucketed) block signature, from the XLA cost model
    ``roofline._aggregate_cost`` reads — memoized on the probe program,
    so it compiles once per signature."""
    import jax

    rows = max(frame.block_sizes or [0])
    if rows <= 0:
        return None
    if bucketing.enabled():
        rows = bucketing.bucket_for(rows)
    specs = {}
    for n in program.input_names:
        col = frame.column(n)
        cell = tuple(np.shape(col.data)[1:])
        st = dtypes.coerce(col.info.scalar_type)
        specs[n] = jax.ShapeDtypeStruct((rows,) + cell, st.np_dtype)
    sig = tuple(
        (n, specs[n].shape, str(specs[n].dtype)) for n in sorted(specs)
    )
    key = ("plan-intensity", sig)
    if key in program._derived:
        return program._derived_hit(key)
    try:
        param_specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
            program._params,
        )
        with observability.suppress_trace_count():
            compiled = program._jit_raw().lower(specs, param_specs).compile()
        flops, nbytes = _roofline._aggregate_cost(compiled)
        intensity = (
            float(flops) / float(nbytes) if flops and nbytes else None
        )
    except Exception:  # noqa: BLE001 - the decision degrades, never fails
        intensity = None
    while len(program._derived) >= program._DERIVED_CAP:
        program._derived.pop(next(iter(program._derived)))
    program._derived[key] = intensity
    return intensity


def _chain_warm(steps: Sequence[PlanStep]) -> bool:
    """Whether every stage's compiled entry already exists (traced by a
    prior planned run OR by the eager verbs — the caches are shared):
    pooling a warm chain costs no first-dispatch compiles."""
    for st in steps:
        prog = st.program
        if st.kind == "map_rows":
            if prog._vmapped is None:
                return False
        elif prog._jitted is None:
            return False
    return True


def _choose_dispatch(
    meta: _FusedMeta, frame: TensorFrame, warm: bool
) -> Dict[str, Any]:
    """The per-group dispatch decision record: ``affinity`` (sharded
    cache resident), ``pool`` (warm executables, or compute-bound per
    the roofline cost model), or ``serial`` (pool unavailable, or a
    cold transfer-bound chain where device-resident serial chaining
    beats paying one compile per device)."""
    rec: Dict[str, Any] = {"warm": bool(warm)}
    if frame_cache.active_cache(frame) is not None:
        rec.update(decision="affinity", reason="sharded_cache_resident")
        return rec
    devs = device_pool.pool_devices()
    rec["devices"] = len(devs)
    if (
        len(devs) < 2
        or frame.num_blocks < 2
        or frame.num_rows == 0
        or not _DEFAULT._frame_fresh(frame)
    ):
        rec.update(decision="serial", reason="pool_unavailable")
        return rec
    # blocks past the engine's chunked-streaming threshold must keep
    # the serial per-stage dispatch: there _stream_plan ingests them
    # chunk-by-chunk with bounded HBM and OOM-split handling, a
    # contract the pooled chain's whole-block device_put would bypass
    chunk = _DEFAULT.stream_chunk_bytes
    if chunk:
        per_row = 0
        for name in meta.src_inputs:
            col = frame.column(name)
            cell = tuple(np.shape(col.data)[1:])
            st = dtypes.coerce(col.info.scalar_type)
            per_row += int(np.prod(cell, dtype=np.int64)) * np.dtype(
                st.np_dtype
            ).itemsize
        if max(frame.block_sizes) * per_row >= 2 * chunk:
            rec.update(decision="serial", reason="stream_chunked_blocks")
            return rec
    if calibrate_enabled():
        # measured-throughput feedback (TFS_PLAN_CALIBRATE): once both
        # dispatch kinds have real measurements for this chain
        # signature, the observed winner overrides the static model
        measured = _calib_lookup(meta, frame)
        if measured and "pool" in measured and "serial" in measured:
            if measured["pool"] >= measured["serial"]:
                rec.update(decision="pool", reason="calibrated_pool")
            else:
                rec.update(decision="serial", reason="calibrated_serial")
            rec["calibration_rows_s"] = {
                k: round(v, 1) for k, v in measured.items()
            }
            return rec
    if warm:
        rec.update(decision="pool", reason="warm_executables")
        return rec
    intensity = _fused_intensity(meta.program, frame)
    rec["intensity_flops_per_byte"] = (
        round(intensity, 4) if intensity is not None else None
    )
    threshold = pool_min_intensity()
    rec["threshold"] = threshold
    if intensity is None or intensity >= threshold:
        rec.update(
            decision="pool",
            reason="no_cost_model" if intensity is None else "compute_bound",
        )
        return rec
    rec.update(decision="serial", reason="transfer_bound_cold")
    return rec


# ---------------------------------------------------------------------------
# fused-chain execution
# ---------------------------------------------------------------------------


def _apply_stages(
    meta: _FusedMeta, staged: Dict[str, Any], donate_entries: bool
) -> Dict[str, Any]:
    """Apply the chain's stages to ONE block's staged inputs via each
    stage program's OWN compiled entry (``jitted``/``vmapped`` — the
    executables the eager verbs run, live params bound), keeping every
    intermediate on the block's device.  Shape hints are re-checked per
    stage exactly like the eager dispatch.

    HBM discipline mirrors the eager pooled loop's: buffers no later
    stage (nor the fetches) reads are DROPPED after each stage, and a
    stage whose every input is a fresh buffer (this call's staged
    entries when ``donate_entries`` — never shards — or an earlier
    stage's intermediate) that dies at this stage runs through the
    engine's DONATING entry, so XLA reuses the input memory for the
    outputs exactly like ``_block_run(program, donate=True)`` does for
    the eager verbs.  Retries are safe by the existing contract: every
    attempt past the first re-stages fresh buffers."""
    donate_ok = prefetch.donate_inputs()
    blk = dict(staged)
    # fresh[c]: buffer c may be donated (created by/for this call only)
    fresh = {c: donate_entries for c in blk}
    for k, st in enumerate(meta.steps):
        prog = st.program
        cols = [prog.column_for_input(n) for n in prog.input_names]
        inputs = {n: blk[c] for n, c in zip(prog.input_names, cols)}
        live = meta.live_after[k]
        donate = (
            donate_ok
            and all(fresh.get(c, False) for c in cols)
            and not (set(cols) & live)
        )
        if st.kind == "map_rows":
            outs = _DEFAULT._rows_run(prog, donate)(inputs)
        else:
            outs = _DEFAULT._block_run(prog, donate)(inputs)
        del inputs
        _check_shape_hints(
            prog, outs, f"plan.{st.label}", cell_level=st.kind == "map_rows"
        )
        if st.trim:
            blk = dict(outs)
            fresh = {}
        else:
            blk.update(outs)
            # free buffers nothing downstream reads (donated ones are
            # dead already; the rest would otherwise pin HBM until the
            # chain ends)
            blk = {c: v for c, v in blk.items() if c in live}
            fresh = {c: f for c, f in fresh.items() if c in live}
        fresh.update({c: True for c in outs})
    return {f: blk[f] for f in meta.fetches}


def _check_chain_outputs(
    meta: _FusedMeta, outs: Dict[str, Any], n_rows: int
) -> None:
    if not meta.trim:
        for name, v in outs.items():
            if v.ndim == 0 or v.shape[0] != n_rows:
                raise ValidationError(
                    f"plan: fused output {name!r} has shape {v.shape} but "
                    f"the input block has {n_rows} rows; a non-trimmed "
                    f"chain must preserve the row count."
                )
    else:
        counts = {v.shape[0] if v.ndim else None for v in outs.values()}
        if len(counts) != 1 or None in counts:
            raise ValidationError(
                f"plan: trimmed chain outputs disagree on row count: "
                f"{ {k: v.shape for k, v in outs.items()} }"
            )


def _chain_pads(
    meta: _FusedMeta, frame: TensorFrame
) -> List[Optional[int]]:
    """Bucket targets for the pooled chain (the engine's
    ``_bucket_plan`` analog): pad each block's entry to its bucket so
    one executable per stage serves every block size — gated on EVERY
    block-level stage passing the jaxpr row-independence proof at the
    exact (real, padded) sizes (map_rows stages are independent by
    construction).  Trimmed chains keep exact shapes (program-defined
    output row counts cannot slice back)."""
    nb = frame.num_blocks
    none: List[Optional[int]] = [None] * nb
    if meta.trim or not bucketing.enabled():
        return none
    sizes = frame.block_sizes
    targets = [
        bucketing.bucket_for(s) if s > 0 else None for s in sizes
    ]
    targets = [
        t if t is not None and t != sizes[i] else None
        for i, t in enumerate(targets)
    ]
    if all(t is None for t in targets):
        return none
    proof_sizes = sorted(
        {sizes[i] for i, t in enumerate(targets) if t is not None}
        | {t for t in targets if t is not None}
    )
    for st, specs in zip(meta.steps, meta.stage_specs):
        if st.kind == "map_rows":
            continue
        if specs is None or not analysis.rows_independent(
            st.program, specs, proof_sizes
        ):
            return none
    return targets


class _TerminalReduce:
    """The fused terminal fold (round 19): the engine-built reduce
    executable (``_reduce_rows_setup``/``_reduce_blocks_setup`` — the
    exact ``run`` the eager verbs dispatch) plus the base -> resolved
    chain-output column map, applied per block INSIDE the pooled chain
    dispatch so no intermediate frame is ever assembled."""

    __slots__ = ("run", "bases", "cols", "sts", "verb")

    def __init__(self, run, bases, cols, sts, verb: str):
        self.run = run
        self.bases = bases
        self.cols = cols
        self.sts = sts
        self.verb = verb


def _chain_fold(
    meta: _FusedMeta,
    terminal: _TerminalReduce,
    staged: Dict[str, Any],
    donate_entries: bool,
    pad: Optional[int],
    n_rows: int,
) -> Optional[Dict[str, Any]]:
    """One block's chain + terminal fold, device-resident end to end:
    apply the stages, slice bucket pads back off, validate, then run the
    reduce executable on the block's device.  Returns None for a block
    whose (trimmed) output has no rows — the eager reduce skips those,
    and the fold shape must match it exactly."""
    outs = _apply_stages(meta, staged, donate_entries=donate_entries)
    if pad is not None:
        outs = {k: v[:n_rows] for k, v in outs.items()}
    _check_chain_outputs(meta, outs, n_rows)
    first = outs[meta.fetches[0]]
    if first.ndim == 0 or first.shape[0] == 0:
        return None
    arrays = {}
    for b in terminal.bases:
        v = outs[terminal.cols[b]]
        dt = terminal.sts[b].np_dtype
        if v.dtype != dt:  # mirror the eager _device_value cast
            v = v.astype(dt)
        arrays[b] = v
    return terminal.run(arrays)


def _run_serial_chain(
    steps: Sequence[PlanStep], frame: TensorFrame
) -> TensorFrame:
    """The fused-serial leg: stages dispatch through the pool-opted-out
    engine — device-resident chaining, only the first stage's inputs
    ever stage H2D, every engine contract (bucketing, streaming,
    donation, retries, empty frames) byte-identical to the eager serial
    path because it IS that path."""
    cur = frame
    for st in steps:
        if st.kind == "map_rows":
            cur = _SERIAL.map_rows(st.program, cur, host_stage=st.host_stage)
        else:
            cur = _SERIAL.map_blocks(
                st.program, cur, trim=st.trim, host_stage=st.host_stage
            )
    return cur


def _run_pooled_chain(
    meta: _FusedMeta,
    frame: TensorFrame,
    cache,
    devices: Sequence[Any],
    terminal: Optional[_TerminalReduce] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """The pooled fused chain: each block stages ONCE (pruned entry
    columns, per-device staging lanes — or resident shards when the
    entry frame is sharded-cached), the whole stage chain runs on the
    block's device, and one overlapped readback window assembles the
    final outputs — the planner's replacement for per-verb pooling's
    host-assembly + re-staging between links.

    Fault tolerance mirrors the engine's pooled loops: retries re-stage
    fresh host buffers on the current effective device and re-run the
    chain; quarantine redirects follow ``PoolRun``.  Outputs are
    donation-adopted as the result frame's shards when sharding
    resolves, with a GC finalizer releasing the budget.

    ``terminal`` (round 19): fold each block's partial on its device
    instead of assembling any output frame — empty blocks are skipped
    (never dispatched), partials hop async to ONE combine device
    (``devices[0]``) in block order, and the return value is
    ``(partials, record)`` for the caller's ``_combine_partials`` —
    byte-for-byte the eager reduce's fold shape."""
    import jax

    sizes = frame.block_sizes
    nb = frame.num_blocks
    offsets = frame.offsets
    assignment = (
        list(cache.assignment)
        if cache is not None
        else device_pool.assign(sizes, len(devices))
    )
    pool = device_pool.PoolRun(
        devices,
        assignment,
        prefetch.prefetch_depth() or 1,
        affinity=cache is not None,
    )
    session = fault_tolerance.frame_session(nb, verb="plan", pool=pool)
    pads = _chain_pads(meta, frame)
    np_dtypes: Dict[str, Any] = {}
    host_cols: Dict[str, np.ndarray] = {}
    for name in meta.src_inputs:
        col = frame.column(name)
        np_dtypes[name] = dtypes.coerce(col.info.scalar_type).np_dtype
        host_cols[name] = np.asarray(col.data)

    def stage_block(bi, dev):
        lo, hi = offsets[bi], offsets[bi + 1]
        staged = {}
        for name in meta.src_inputs:
            a = host_cols[name][lo:hi]
            if a.dtype != np_dtypes[name]:
                a = a.astype(np_dtypes[name])
            if pads[bi] is not None:
                a = bucketing.pad_rows(a, pads[bi])
            observability.note_h2d_bytes(a.nbytes)
            staged[name] = jax.device_put(a, dev)
        return staged

    def stage_cached(bi, dev_i):
        """Entry dict for one sharded-cached block: resident shard
        columns pass through on their device; missing columns and
        evicted blocks re-stage from the authoritative host copy."""
        shard = cache.shard(bi) if dev_i == assignment[bi] else None
        lo, hi = offsets[bi], offsets[bi + 1]
        staged = {}
        used = False
        for name in meta.src_inputs:
            v = shard.get(name) if shard is not None else None
            if v is not None:
                if pads[bi] is not None:
                    v = bucketing.pad_rows(v, pads[bi])
                staged[name] = v
                used = True
                continue
            a = host_cols[name][lo:hi]
            if a.dtype != np_dtypes[name]:
                a = a.astype(np_dtypes[name])
            if pads[bi] is not None:
                a = bucketing.pad_rows(a, pads[bi])
            observability.note_h2d_bytes(a.nbytes)
            staged[name] = jax.device_put(a, devices[dev_i])
        return staged, used

    if cache is None:
        lanes = device_pool.lanes(
            devices, assignment, stage_block, name="tfs-plan"
        )
        lane_iters = [iter(ln) for ln in lanes]
        lane_dead = [False] * len(devices)
    else:
        lanes = []
    out_blocks: List[Optional[Dict[str, Any]]] = [None] * nb
    adopt_outs = (
        [None] * nb
        if (
            terminal is None
            and (
                cache is not None
                or len(frame_cache.shard_devices(None)) >= 2
            )
        )
        else None
    )
    partials: List[Dict[str, Any]] = []
    combine = devices[0]
    eff_assign: List[int] = []
    shard_hits = 0
    for bi in range(nb):
        cancellation.checkpoint()  # block boundary (pooled chain)
        di = assignment[bi]
        sp = observability.span(
            "plan.block", f"device/{di}",
            verb=terminal.verb if terminal is not None else "map",
            block=bi, rows=sizes[bi], device=di,
        )
        if terminal is not None and sizes[bi] == 0:
            # the eager reduce never dispatches empty blocks; consume
            # the staged lane entry so later blocks stay aligned
            if cache is None:
                if session is None:
                    next(lane_iters[di])
                else:
                    block_loop.lane_next(
                        lane_iters[di], lane_dead, di, session, pool
                    )
            eff_assign.append(di)
            continue
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
        elif session is None:
            staged = next(lane_iters[di])
        else:
            staged = block_loop.lane_next(
                lane_iters[di], lane_dead, di, session, pool
            )
        if session is None:
            if terminal is not None:
                # chain + fold, device-resident: no assembly, no frame
                p = _chain_fold(
                    meta, terminal, staged, cache is None,
                    pads[bi], sizes[bi],
                )
            else:
                # entry buffers donate only when freshly staged this
                # call (never resident shards — shared frame state)
                outs = _apply_stages(
                    meta, staged, donate_entries=cache is None
                )
            del staged
            di_eff = di
        else:
            holder = {"v": staged}
            del staged

            def attempt(a, dev_i, _bi=bi, _h=holder, _di=di):
                # attempt 0 may consume the staged entry; every retry
                # (and any quarantine redirect) re-stages fresh host
                # buffers on the CURRENT device and re-runs the chain
                ins = _h.pop("v", None) if (a == 0 and dev_i == _di) else None
                _h.clear()
                restaged = ins is None
                if ins is None:
                    ins = stage_block(_bi, devices[dev_i])
                # re-staged buffers are fresh even for cached frames;
                # attempt-0 entries are fresh only without a cache
                if terminal is not None:
                    # the fold rides inside the attempt so a fault at
                    # the reduce dispatch retries the whole block
                    return _chain_fold(
                        meta, terminal, ins, restaged or cache is None,
                        pads[_bi], sizes[_bi],
                    )
                return _apply_stages(
                    meta, ins, donate_entries=restaged or cache is None
                )

            res = session.run(
                bi,
                sizes[bi],
                attempt,
                device=lambda _di=di: pool.effective_device(_di),
            )
            if terminal is not None:
                p = res
            else:
                outs = res
            di_eff = pool.effective_device(di)
        if terminal is not None:
            if p is not None:
                # async hop to the combine device, one reduced cell per
                # base, in block order — the eager partials' exact shape
                partials.append(
                    {
                        b: jax.device_put(p[b], combine)
                        for b in terminal.bases
                    }
                )
            eff_assign.append(di_eff)
            pool.note_dispatch(di_eff, sizes[bi])
            sp.track = f"device/{di_eff}"
            sp.end(device=di_eff)
            continue
        if pads[bi] is not None:
            # bucket-padded chain: slice the pad rows back off (the
            # per-stage proofs guarantee real rows' values)
            outs = {k: v[: sizes[bi]] for k, v in outs.items()}
        _check_chain_outputs(meta, outs, sizes[bi])
        if adopt_outs is not None:
            adopt_outs[bi] = outs
        eff_assign.append(di_eff)
        pool.submit(bi, di_eff, sizes[bi], outs, out_blocks)
        sp.track = f"device/{di_eff}"
        sp.end(device=di_eff)
    pool.finish(out_blocks)
    if terminal is not None:
        rec = {
            "device_pool": pool.record(
                sum(ln.stats["stage_s"] for ln in lanes),
                sum(ln.stats["wait_s"] for ln in lanes),
            )
        }
        if cache is not None:
            fc = cache.record()
            fc["shard_hits"] = shard_hits
            rec["frame_cache"] = fc
        if session is not None and session.events():
            rec["fault_tolerance"] = session.record()
        return partials, rec
    out_frame = TensorFrame.from_blocks(out_blocks)
    if not meta.trim:
        # source columns not shadowed by chain outputs pass through
        # unchanged — including the PRUNED ones, host-side, zero staging
        extra = [
            c
            for c in frame.columns
            if c.info.name not in out_frame.column_names
        ]
        if extra:
            out_frame = TensorFrame(
                list(out_frame.columns) + extra, out_frame.offsets
            )
    rec: Dict[str, Any] = {
        "device_pool": pool.record(
            sum(ln.stats["stage_s"] for ln in lanes),
            sum(ln.stats["wait_s"] for ln in lanes),
        )
    }
    if cache is not None:
        fc = cache.record()
        fc["shard_hits"] = shard_hits
        rec["frame_cache"] = fc
    if session is not None and session.events():
        rec["fault_tolerance"] = session.record()
    adopted = (
        frame_cache.adopt(out_frame, devices, eff_assign, adopt_outs)
        if adopt_outs is not None
        else None
    )
    if adopted is not None:
        # planner-created cache: refund the HBM budget at frame GC
        weakref.finalize(out_frame, _release_cache, adopted)
        observability.note_plan_cache_insert()
        rec["adopted_blocks"] = adopted.resident_blocks()
    return out_frame, rec


# ---------------------------------------------------------------------------
# cross-plan common-subexpression sharing (round 19)
# ---------------------------------------------------------------------------
#
# A process-wide plan-signature registry: two planned executions of an
# IDENTICAL subplan — same source frame object, same step Program
# objects at the same live-params generation, same terminal pruning —
# execute it once.  Concurrent requests rendezvous on an in-flight
# entry: the first claimant (the owner) runs the segment under a
# PRIVATE root ledger, and at completion every consumer registered so
# far (owner + waiters) absorbs an exact integer share of the measured
# counters/blocks/rows (`RequestLedger.absorb`, the coalescer's round-16
# attribution contract) — so per-request ledgers still SUM to the
# global counters delta bit for bit.  Later identical chains reuse the
# shared result while it is alive (`plan_cse_hits`); signatures embed
# object ids but every entry holds weakrefs, so a recycled id can never
# alias stale results onto different frames/programs.


def _apportion_even(total: int, k: int) -> List[int]:
    """Split ``total`` into ``k`` equal integer shares that sum exactly
    (the shared :func:`observability.apportion` with unit weights — one
    implementation of the attribution-critical split, not two)."""
    return observability.apportion(int(total), [1] * k)


def _plan_signature(
    nodes: Sequence["LazyFrame"],
    frame: TensorFrame,
    keep: Optional[Set[str]],
) -> Optional[Tuple]:
    steps = []
    for nd in nodes:
        st = nd._step
        if st is None or st.stage_bound:
            # host-staged stages run arbitrary python per dispatch —
            # never share their results
            return None
        prog = st.program
        steps.append(
            (
                st.kind,
                st.trim,
                id(prog),
                getattr(prog, "_params_version", 0),
            )
        )
    return (
        id(frame),
        frame.num_rows,
        frame.num_blocks,
        _entry_signature(frame),
        tuple(steps),
        None if keep is None else tuple(sorted(keep)),
    )


class _ReduceResult(dict):
    """A reduce-terminal CSE result: plain dicts cannot carry weak
    references, and the registry holds completed results by weakref
    only (so cached outputs never outlive their consumers).  Behaves
    exactly like the ``{base: ndarray}`` dict it wraps."""

    __slots__ = ("__weakref__",)


class _CseEntry:
    __slots__ = (
        "event",
        "consumers",
        "done",
        "failed",
        "frame_wr",
        "guards",
    )

    def __init__(self, frame, nodes):
        self.event = threading.Event()
        # (ledger-or-None, slot) per consumer registered before
        # completion; the owner's pair is consumers[0]
        self.consumers: List[Tuple[Any, Dict[str, Any]]] = []
        self.done = False
        self.failed = False
        self.frame_wr = None
        self.guards = [weakref.ref(frame)] + [
            weakref.ref(nd._step.program) for nd in nodes
        ]

    def valid(self) -> bool:
        return all(g() is not None for g in self.guards)


class _PlanRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[Tuple, _CseEntry]" = (
            collections.OrderedDict()
        )
        # signature -> {"executions", "hits", "stages"}; survives result
        # GC so tfs.doctor()'s cse_miss rule can see repeat executions
        self._stats: "collections.OrderedDict[Tuple, Dict[str, int]]" = (
            collections.OrderedDict()
        )
        self._cap = 256

    def _stat(self, sig: Tuple, stages: int) -> Dict[str, int]:
        rec = self._stats.setdefault(
            sig, {"executions": 0, "hits": 0, "stages": stages}
        )
        self._stats.move_to_end(sig)
        while len(self._stats) > self._cap:
            self._stats.popitem(last=False)
        return rec

    def lookup_or_claim(
        self, sig: Tuple, frame: TensorFrame, nodes: Sequence["LazyFrame"]
    ) -> Tuple:
        """("hit", frame) | ("wait", slot, event) | ("own", entry)."""
        with self._lock:
            for key in [
                k for k, e in self._entries.items() if not e.valid()
            ]:
                del self._entries[key]
            ent = self._entries.get(sig)
            if ent is not None:
                if ent.done and not ent.failed:
                    out = ent.frame_wr() if ent.frame_wr else None
                    if out is not None:
                        self._stat(sig, len(nodes))["hits"] += 1
                        self._entries.move_to_end(sig)
                        return ("hit", out)
                    # result was garbage-collected: execute afresh
                elif not ent.done:
                    slot: Dict[str, Any] = {}
                    ent.consumers.append(
                        (observability.current_request(), slot)
                    )
                    # a rendezvous IS a share: count it here so the
                    # cse_miss doctor rule cannot fire on workloads
                    # whose sharing is always concurrent (the owner
                    # failing is the rare corner this may overcount)
                    self._stat(sig, len(nodes))["hits"] += 1
                    return ("wait", slot, ent.event)
            ent = _CseEntry(frame, nodes)
            ent.consumers.append(
                (observability.current_request(), {})
            )
            self._entries[sig] = ent
            self._stat(sig, len(nodes))["executions"] += 1
            while len(self._entries) > self._cap:
                _, old = self._entries.popitem(last=False)
                if not old.done:
                    old.failed = True
                    old.done = True
                    old.event.set()
            return ("own", ent)

    def complete(self, sig: Tuple, ent: _CseEntry, out, led) -> None:
        """Owner finished: deliver the frame to every waiter, apportion
        the private ledger's exact delta across all consumers
        registered by now, and downgrade the entry to a weakref.
        Waiters that ABANDONED the rendezvous (woken early by a cap
        eviction and already paying their own execution) are excluded —
        absorbing a share on top of their own full delta would
        double-bill their request ledgers."""
        counters = {k2: v for k2, v in led.counters.items() if v}
        blocks = dict(led.blocks_per_device)
        # snapshot, absorb, and delivery all under the registry lock:
        # an abandoning waiter (cap-evicted rendezvous) flips its flag
        # under the same lock, so it is either excluded here or finds
        # its frame delivered — never both billed and self-paying.
        # Lock order is registry -> ledger only; ledger locks are leaf.
        with self._lock:
            consumers = [
                c for c in ent.consumers if not c[1].get("abandoned")
            ]
            ent.frame_wr = weakref.ref(out)
            ent.done = True
            k = len(consumers)
            shares = {
                k2: _apportion_even(v, k) for k2, v in counters.items()
            }
            block_shares = {
                d: _apportion_even(v, k) for d, v in blocks.items()
            }
            row_shares = _apportion_even(led.rows, k)
            for i, (consumer_led, slot) in enumerate(consumers):
                if consumer_led is not None:
                    consumer_led.absorb(
                        {k2: s[i] for k2, s in shares.items()},
                        {d: s[i] for d, s in block_shares.items()},
                        row_shares[i],
                    )
                slot["frame"] = out
            # waiters hold their own slot references; dropping the list
            # keeps the registry from pinning result frames alive
            ent.consumers = []
        ent.event.set()

    def fail(self, sig: Tuple, ent: _CseEntry) -> None:
        with self._lock:
            ent.failed = True
            ent.done = True
            if self._entries.get(sig) is ent:
                del self._entries[sig]
        ent.event.set()

    def stats(self) -> List[Dict[str, int]]:
        with self._lock:
            return [dict(v) for v in self._stats.values()]


_REGISTRY = _PlanRegistry()


def recent_plan_stats() -> List[Dict[str, int]]:
    """Per-signature execution/hit counts from the CSE registry — the
    evidence behind ``tfs.doctor()``'s ``cse_miss`` rule (injectable
    there as ``plans=``)."""
    return _REGISTRY.stats()


def _cse_execute(
    nodes: List["LazyFrame"],
    frame: TensorFrame,
    records: List[Dict],
    start_idx: int,
    cse: bool = True,
    keep: Optional[Set[str]] = None,
) -> TensorFrame:
    """Execute one flush segment through the CSE registry: reuse a live
    identical result, rendezvous with an in-flight execution, or own the
    execution under a private root ledger and apportion its exact cost
    across every consumer registered by completion."""
    sig = (
        _plan_signature(nodes, frame, keep)
        if (cse and cse_enabled())
        else None
    )
    if sig is None:
        return _flush(nodes, frame, records, start_idx, keep=keep)
    claim = _REGISTRY.lookup_or_claim(sig, frame, nodes)
    verb = "+".join(nd._step.label for nd in nodes)
    if claim[0] == "hit":
        observability.note_plan_cse_hit()
        records.append(
            {
                "stage": start_idx,
                "verb": verb,
                "fused": len(nodes),
                "dispatch": "cse",
                "reason": "registry_hit",
                "rows": claim[1].num_rows,
            }
        )
        return claim[1]
    if claim[0] == "wait":
        _, slot, event = claim
        try:
            while not event.wait(0.05):
                cancellation.checkpoint()  # deadlines cut the wait too
        except BaseException:
            # cancelled mid-rendezvous: renounce the share UNDER THE
            # LOCK so the owner's complete() cannot bill this request
            # for a result it never received (if the frame was already
            # delivered, the absorbed share legitimately stands)
            with _REGISTRY._lock:
                if slot.get("frame") is None:
                    slot["abandoned"] = True
            raise
        out = slot.get("frame")
        if out is None:
            # woken without a result (owner failed, or the entry was
            # cap-evicted mid-flight): declare the rendezvous abandoned
            # UNDER THE LOCK so a late complete() cannot also absorb a
            # share for us, then re-check — the flag and the delivery
            # are ordered by the registry lock
            with _REGISTRY._lock:
                if slot.get("frame") is None:
                    slot["abandoned"] = True
            out = slot.get("frame")
        if out is not None:
            observability.note_plan_cse_hit()
            records.append(
                {
                    "stage": start_idx,
                    "verb": verb,
                    "fused": len(nodes),
                    "dispatch": "cse",
                    "reason": "shared_inflight",
                    "rows": out.num_rows,
                }
            )
            return out
        # the owner failed (or was evicted mid-flight): pay our own way
        return _flush(nodes, frame, records, start_idx, keep=keep)
    ent = claim[1]
    # the owner's execution runs under a PRIVATE root ledger so its
    # delta can be apportioned exactly; the suspended request context
    # gets its share back through absorb (consumers[0] is the owner)
    tok0 = observability.activate_request(None)
    led = observability.RequestLedger(method="plan_cse")
    tok1 = observability.activate_request(led)
    try:
        out = _flush(nodes, frame, records, start_idx, keep=keep)
    except BaseException:
        observability.deactivate_request(tok1)
        observability.deactivate_request(tok0)
        _REGISTRY.fail(sig, ent)
        raise
    observability.deactivate_request(tok1)
    observability.deactivate_request(tok0)
    _REGISTRY.complete(sig, ent, out, led)
    return out


# ---------------------------------------------------------------------------
# the lazy frame
# ---------------------------------------------------------------------------


class LazyFrame:
    """A frame whose verbs build a logical plan (``frame.lazy()``).

    Nodes form a DAG: each derived LazyFrame holds its parent strongly
    (the plan must survive) and parents hold children weakly (consumer
    bookkeeping must not leak).  Materialisation memoizes the executed
    frame on the node, so a shared subplan executes once; a node with
    two or more consumers becomes an optimization *barrier* and — when a
    device pool is available — gets an auto-inserted sharded cache over
    the columns its consumers read.

    Any TensorFrame attribute not defined here (``collect``,
    ``to_arrays``, ``column``, ``schema``, …) materialises the plan and
    delegates — the lazy surface is a superset of the eager one."""

    _tfs_lazy = True

    def __init__(
        self,
        source: Optional[TensorFrame] = None,
        parent: Optional["LazyFrame"] = None,
        step: Optional[PlanStep] = None,
    ):
        if (source is None) == (parent is None):
            raise ValidationError(
                "LazyFrame: exactly one of source/parent is required"
            )
        self._source = source
        self._parent = parent
        self._step = step
        self._child_refs: List[Any] = []
        self._children = 0  # registered consumers (derived + terminal)
        self._materialized: Optional[TensorFrame] = (
            source if step is None else None
        )
        self._mat_uses = 0  # dispatch-consumptions of the memoized frame
        self._auto_cached = False
        self._finalizer = None
        self._last_records: List[Dict[str, Any]] = []
        self._runs = 0  # times this node's step has executed

    # -- plan building -------------------------------------------------------

    def lazy(self) -> "LazyFrame":
        return self

    # guards shared plan-tree bookkeeping (root get-or-create, child
    # registration): concurrent bridge requests append chains to ONE
    # shared per-frame root, and unlocked read-modify-writes there
    # would lose consumer counts / drop live child refs — starving the
    # auto-cache trigger and _needed_below's cached-column set
    _TREE_LOCK = threading.Lock()

    def _bump(self, attr: str) -> int:
        """Locked increment for shared-node consumer bookkeeping
        (``_children``/``_mat_uses``): concurrent requests off one
        shared root must not lose counts — the auto-cache trigger
        reads them."""
        with LazyFrame._TREE_LOCK:
            v = getattr(self, attr) + 1
            setattr(self, attr, v)
            return v

    def _append(
        self,
        kind: str,
        program: Program,
        trim: bool = False,
        host_stage: Optional[Mapping[str, Any]] = None,
    ) -> "LazyFrame":
        step = PlanStep(kind, program, trim=trim, host_stage=host_stage)
        child = LazyFrame(parent=self, step=step)
        with LazyFrame._TREE_LOCK:
            if len(self._child_refs) >= 32:
                # epochs loops re-derive from one shared root every
                # pass: drop dead consumer refs so the list stays
                # bounded by the LIVE fan-out, not the plan's lifetime
                self._child_refs = [
                    r for r in self._child_refs if r() is not None
                ]
            self._child_refs.append(weakref.ref(child))
            self._children += 1  # lock already held (non-reentrant)
        return child

    def group_by(self, *keys: str) -> GroupedFrame:
        """Group for ``aggregate``.  An unmaterialised plan defers the
        materialisation to the aggregate itself (round 19): the
        aggregate then knows exactly which chain outputs it reads, so
        the one materialisation it still needs (group structure is
        data-dependent) fetches ONLY the key + reduced columns.  Key
        contracts are still checked HERE whenever the chain's schema is
        statically known — deferral must not move the eager call-site
        error to aggregate time."""
        self._bump("_children")
        if self._materialized is not None:
            return GroupedFrame(self._materialized, keys)
        if keys:
            self._check_group_keys(keys)
        return LazyGroupedFrame(self, keys)

    def _check_group_keys(self, keys: Sequence[str]) -> None:
        """The eager ``GroupedFrame`` constructor's key checks, run
        against the chain's statically inferred output schema (entry
        columns + analyzed derived columns).  An opaque chain (host
        stages, unresolvable inputs) defers to aggregate time."""
        chain: List[LazyFrame] = []
        cur = self
        while cur._materialized is None:
            chain.append(cur)
            cur = cur._parent
        chain.reverse()
        src = cur._materialized
        if src is None or not chain:
            return
        steps = [nd._step for nd in chain]
        n, _, _ = _fusable_run(steps, _device_infos(src))
        if n != len(steps):
            return  # schema not statically known: checked at aggregate
        meta = _compose(steps, src)
        shim = _SchemaShim(src, meta.final_infos, trim=meta.trim)
        for k in keys:
            ci = shim.schema[k]  # raises SchemaError exactly like eager
            if ci.cell_shape.rank != 0:
                raise ValidationError(
                    f"group_by: key column {k!r} must be scalar, has "
                    f"cell shape {ci.cell_shape}"
                )

    def frame(self) -> TensorFrame:
        """Force execution and return the materialised TensorFrame."""
        return self._materialize(count_use=False)

    # -- execution -----------------------------------------------------------

    def _materialize(
        self,
        needed_hint: Optional[Set[str]] = None,
        count_use: bool = True,
        keep: Optional[Set[str]] = None,
        cse: bool = True,
    ) -> TensorFrame:
        """Execute the plan.  ``keep`` (round 19): prune the FINAL fused
        group's fetches to the named derived columns (a terminal
        consumer's read set) — the result is then partial by design and
        is NOT memoized on the node.  ``cse=False`` bypasses the
        cross-plan registry (per-window streaming plans, whose source
        frames never repeat)."""
        if self._materialized is not None:
            if count_use:
                self._bump("_mat_uses")
                if self._mat_uses >= 2:
                    self._ensure_auto_cache(needed_hint)
            return self._materialized

        # the chain of unmaterialised steps back to the nearest memo/root
        chain: List[LazyFrame] = []
        cur = self
        while cur._materialized is None:
            chain.append(cur)
            cur = cur._parent
        chain.reverse()
        entry = cur
        frame = entry._materialized
        # one more dispatch reads the shared entry: promote it to an
        # auto cache on its second consumption (the epochs pattern)
        entry._bump("_mat_uses")
        if entry._mat_uses >= 2:
            entry._ensure_auto_cache(_first_step_cols(chain) or needed_hint)

        records: List[Dict[str, Any]] = []
        with observability.verb_span(
            "plan", frame.num_rows, frame.num_blocks
        ) as span:
            pending: List[LazyFrame] = []
            done = 0
            for nd in chain:
                pending.append(nd)
                if nd._children >= 2 and nd is not chain[-1]:
                    # shared subplan: materialisation barrier + cache
                    frame = _cse_execute(
                        pending, frame, records, done, cse=cse
                    )
                    done += len(pending)
                    pending = []
                    nd._materialized = frame
                    nd._mat_uses = 1
                    nd._ensure_auto_cache(None)
                    frame = nd._materialized
            if pending:
                frame = _cse_execute(
                    pending, frame, records, done, cse=cse, keep=keep
                )
            span.annotate(
                "planner",
                {
                    "stages": records,
                    "fused_groups": sum(
                        1 for r in records if r.get("fused", 0) >= 2
                    ),
                    "pruned_columns": sorted(
                        {c for r in records for c in r.get("pruned", ())}
                    ),
                },
            )
        if keep is None:
            self._materialized = frame
            self._mat_uses = 1
        self._last_records = records
        return frame

    # -- auto cache ----------------------------------------------------------

    # serializes auto-cache insertion across threads: concurrent bridge
    # requests materializing off one shared root must not both pass the
    # check-then-act and build two caches for one frame (the loser's
    # shards would stay charged against TFS_HBM_BUDGET until frame GC)
    _AUTOCACHE_LOCK = threading.Lock()

    def _ensure_auto_cache(
        self, needed_hint: Optional[Set[str]] = None
    ) -> None:
        """Insert the sharded cache on this node's materialised frame,
        over the columns downstream consumers read — once, and only when
        shard placement resolves (>= 2 pool devices per
        ``TFS_CACHE_SHARDED``'s auto rule, exactly like ``cache()``'s
        default).  A ``weakref.finalize`` on the frame releases the
        shards when the planned frame is garbage-collected, refunding
        ``TFS_HBM_BUDGET`` deterministically instead of waiting for a
        later charge walk to prune the dead entries."""
        mat = self._materialized
        if mat is None or self._auto_cached:
            return
        with LazyFrame._AUTOCACHE_LOCK:
            if self._auto_cached:
                return
            if frame_cache.active_cache(mat) is not None:
                self._auto_cached = True  # adopted / user-cached already
                return
            devs = frame_cache.shard_devices(None)
            if len(devs) < 2:
                return
            needed, everything = self._needed_below()
            if needed_hint:
                needed |= set(needed_hint)
            cacheable = [
                name
                for name in _device_infos(mat)
                if not mat.column(name).is_device
                and (everything or name in needed)
            ]
            if not cacheable:
                return
            cache = frame_cache.build(mat, sorted(cacheable), devices=devs)
            if cache is None:
                return
            frame_cache.attach(mat, cache)
            self._finalizer = weakref.finalize(mat, _release_cache, cache)
            self._auto_cached = True
        observability.note_plan_cache_insert()
        _log.info(
            "planner: auto-inserted sharded cache over %s (%d consumers)",
            cacheable,
            max(self._children, self._mat_uses),
        )

    def _needed_below(self) -> Tuple[Set[str], bool]:
        """Columns of this node's frame that registered downstream
        stages consume (transitively), plus an everything flag when a
        host-staged descendant makes the set unknowable.
        Over-approximation is safe: the host copy stays authoritative,
        extra shards are only bytes."""
        needed: Set[str] = set()
        everything = False
        for ref in self._child_refs:
            child = ref()
            if child is None or child._step is None:
                continue
            st = child._step
            if st.stage_bound:
                everything = True
            needed.update(
                st.program.column_for_input(n)
                for n in st.program.input_names
            )
            sub, all_flag = child._needed_below()
            needed |= sub
            everything = everything or all_flag
        return needed, everything

    # -- terminal verbs ------------------------------------------------------

    def _reduce(self, verb: str, program: Program, mode: str = "tree"):
        self._bump("_children")
        if self._materialized is None:
            out = self._cse_reduce(verb, program, mode)
            if out is not None:
                return out
        mat = self._materialize(needed_hint=_reduce_cols(program))
        if verb == "reduce_rows":
            return _DEFAULT.reduce_rows(program, mat, mode=mode)
        return _DEFAULT.reduce_blocks(program, mat)

    def _cse_reduce(self, verb: str, program: Program, mode):
        """Route the fused terminal reduce through the CSE registry
        (round-22 close of the round-19 residual): concurrent requests
        ending in the SAME fused reduce over the SAME chain rendezvous
        and execute once, with the owner's private-ledger delta
        apportioned exactly across every consumer — the same share
        semantics map-terminal plans already have.  The signature is
        the chain's plan signature extended with the reduce's identity
        (verb, mode, program), and the entry additionally guards on the
        reduce program's lifetime.  Falls back to a solo
        ``_fused_terminal_reduce`` whenever the signature cannot be
        built (host stages, CSE off); a ``None`` from the fused path
        (pre-dispatch bail: serial decision, trimmed chain, source
        column read) fails the entry so waiters pay their own way, and
        the caller falls through to materialize-then-reduce."""
        if not cse_enabled():
            return self._fused_terminal_reduce(verb, program, mode)
        tc = self._terminal_chain()
        if tc is None:
            # cheap pre-check: no fusable chain means the fused path
            # bails immediately anyway — don't mint registry entries
            # for plans that always materialize
            return self._fused_terminal_reduce(verb, program, mode)
        _entry, chain, _steps, frame = tc
        base_sig = _plan_signature(chain, frame, None)
        if base_sig is None:
            return self._fused_terminal_reduce(verb, program, mode)
        sig = base_sig + (
            (
                "reduce",
                verb,
                mode,
                id(program),
                getattr(program, "_params_version", 0),
            ),
        )
        claim = _REGISTRY.lookup_or_claim(sig, frame, chain)
        label = (
            "+".join(nd._step.label for nd in chain) + f"+{verb}"
        )
        if claim[0] == "hit":
            observability.note_plan_cse_hit()
            self._last_records = [
                {
                    "stage": 0,
                    "verb": label,
                    "fused": len(chain) + 1,
                    "dispatch": "cse",
                    "reason": "registry_hit",
                    "terminal": verb,
                }
            ]
            return claim[1]
        if claim[0] == "wait":
            _, slot, event = claim
            try:
                while not event.wait(0.05):
                    cancellation.checkpoint()
            except BaseException:
                with _REGISTRY._lock:
                    if slot.get("frame") is None:
                        slot["abandoned"] = True
                raise
            out = slot.get("frame")
            if out is None:
                with _REGISTRY._lock:
                    if slot.get("frame") is None:
                        slot["abandoned"] = True
                out = slot.get("frame")
            if out is not None:
                observability.note_plan_cse_hit()
                self._last_records = [
                    {
                        "stage": 0,
                        "verb": label,
                        "fused": len(chain) + 1,
                        "dispatch": "cse",
                        "reason": "shared_inflight",
                        "terminal": verb,
                    }
                ]
                return out
            # owner failed or bailed to the materialized path: run our
            # own fused attempt (it may bail to materialize too)
            return self._fused_terminal_reduce(verb, program, mode)
        ent = claim[1]
        # the chain guards came from lookup_or_claim; the reduce
        # program's lifetime guards this entry too (its id is in the
        # signature — an id reused by a NEW program must not hit)
        ent.guards.append(weakref.ref(program))
        tok0 = observability.activate_request(None)
        led = observability.RequestLedger(method="plan_cse")
        tok1 = observability.activate_request(led)
        try:
            out = self._fused_terminal_reduce(verb, program, mode)
        except BaseException:
            observability.deactivate_request(tok1)
            observability.deactivate_request(tok0)
            _REGISTRY.fail(sig, ent)
            raise
        observability.deactivate_request(tok1)
        observability.deactivate_request(tok0)
        if out is None:
            # pre-dispatch bail: nothing executed, nothing to share —
            # waiters wake, fall back, and pay their own (cheap) way
            _REGISTRY.fail(sig, ent)
            return None
        out = _ReduceResult(out)
        _REGISTRY.complete(sig, ent, out, led)
        return out

    def _terminal_chain(self):
        """The unmaterialised step chain back to the nearest memo/root,
        or None when a terminal fusion cannot apply: no steps, an
        interior shared subplan (its memoized barrier is worth more than
        the fold), or an unfusable run (host stages, ragged inputs)."""
        chain: List[LazyFrame] = []
        cur = self
        while cur._materialized is None:
            chain.append(cur)
            cur = cur._parent
        chain.reverse()
        frame = cur._materialized
        if not chain or frame.num_rows == 0:
            return None
        if any(nd._children >= 2 for nd in chain[:-1]):
            return None
        steps = [nd._step for nd in chain]
        n, _, _ = _fusable_run(steps, _device_infos(frame))
        if n != len(steps):
            return None
        return cur, chain, steps, frame

    def _fused_terminal_reduce(self, verb: str, program: Program, mode):
        """The round-19 fused terminal fold: when the whole pending
        chain is one fusable run, its dispatch would pool, and every
        reduce base resolves to a chain output, fold each block's
        partial inside the pooled chain dispatch — no intermediate
        frame is ever assembled (no D2H readback, no re-staging H2D) —
        then finish with the engine's own ``_combine_partials``.
        Returns None whenever the eager materialize-then-reduce path
        should run instead (bit-identical either way: the fold shape,
        executables, and combine device are the eager ones)."""
        tc = self._terminal_chain()
        if tc is None:
            return None
        entry, chain, steps, frame = tc
        meta0 = _compose(steps, frame)
        if meta0.trim:
            # trimmed chains have program-defined per-block row counts;
            # the materialized path keeps their contract checks simple
            return None
        # the engine's own setup over the chain's inferred output
        # schema — contract violations surface exactly like eager
        shim = _SchemaShim(frame, meta0.final_infos)
        if verb == "reduce_rows":
            bases, reduced, run = _DEFAULT._reduce_rows_setup(
                program, shim, mode
            )
        else:
            bases, reduced, run = _DEFAULT._reduce_blocks_setup(
                program, shim
            )
        cols = {b: reduced[b].name for b in bases}
        if not all(cols[b] in set(meta0.fetches) for b in bases):
            # the reduce reads a source/passthrough column the chain
            # does not produce: materialize (it must be staged anyway)
            return None
        meta = _compose(steps, frame, keep=set(cols.values()))
        warm = any(nd._runs > 0 for nd in chain) or _chain_warm(steps)
        rec = _choose_dispatch(meta, frame, warm)
        decision = rec.pop("decision")
        reason = rec.pop("reason")
        if decision not in ("pool", "affinity"):
            # serial: the fused-serial chain + eager reduce IS the
            # baseline (device-resident, single device) — no round trip
            # to eliminate
            return None
        sts = {b: dtypes.coerce(reduced[b].scalar_type) for b in bases}
        terminal = _TerminalReduce(run, bases, cols, sts, verb)
        # one more consumption of the shared entry (epochs promotion)
        entry._bump("_mat_uses")
        if entry._mat_uses >= 2:
            entry._ensure_auto_cache(_first_step_cols(chain))
        records: List[Dict[str, Any]] = []
        with observability.verb_span(
            "plan", frame.num_rows, frame.num_blocks
        ) as span:
            cache = frame_cache.active_cache(frame)
            devices = (
                cache.devices
                if cache is not None
                else device_pool.pool_devices()
            )
            (partials, run_rec), measured = _measured(
                lambda: _run_pooled_chain(
                    meta, frame, cache, devices, terminal=terminal
                ),
                frame.num_rows,
            )
            rec.update(run_rec)
            rec.update(measured)
            # feed the calibration table too (keep-pruned fetch key —
            # a different workload from the full chain's); terminal
            # chains only ever measure the pooled side (their serial
            # decision falls back to materialize-then-reduce), so the
            # calibrated override stays inert for them until a serial
            # measurement exists — one-sided entries never decide
            _calib_note(
                meta, frame, decision, measured.get("rows_per_s")
            )
            if len(steps) >= 2:
                observability.note_plan_fused_dispatch()
            observability.note_plan_fused_reduce()
            if meta.pruned:
                observability.note_plan_columns_pruned(len(meta.pruned))
            records.append(
                {
                    "stage": 0,
                    "verb": "+".join(st.label for st in steps)
                    + f"+{verb}",
                    "fused": len(steps) + 1,
                    "dispatch": decision,
                    "reason": reason,
                    "terminal": verb,
                    "pruned": list(meta.pruned),
                    **rec,
                }
            )
            final = _DEFAULT._combine_partials(run, bases, partials)
            out = {b: _np(final[b]) for b in bases}
            span.annotate(
                "planner",
                {
                    "stages": records,
                    "fused_groups": 1,
                    "fused_terminal": verb,
                },
            )
        for nd in chain:
            nd._runs += 1
        self._last_records = records
        return out

    def _aggregate_terminal(
        self,
        program: Program,
        keys: Sequence[str],
        grouped: Optional["LazyGroupedFrame"] = None,
    ) -> TensorFrame:
        """Terminal-pruned aggregate (round 19): materialise the chain
        fetching ONLY the key + reduced columns the aggregate reads
        (everything else is never assembled to host), then run the
        UNCHANGED eager aggregate over it — grouping numerics are the
        eager engine's, bit for bit.

        Repeat aggregates over one ``grouped`` handle stay
        materialize-once: a pruned result is memoized on the handle per
        read set, and a SECOND aggregate with a different read set
        switches to the full (node-memoized) materialisation — the
        round-14 behavior — instead of re-executing the chain per
        program."""
        from .validation import check_reduce_blocks

        tc = self._terminal_chain()
        if tc is None or self._materialized is not None:
            mat = self._materialize(needed_hint=set(keys))
            return _DEFAULT.aggregate(program, GroupedFrame(mat, keys))
        entry, chain, steps, frame = tc
        meta0 = _compose(steps, frame)
        shim = _SchemaShim(frame, meta0.final_infos, trim=meta0.trim)
        reduced = check_reduce_blocks(program, shim, verb="aggregate")
        needed = set(keys) | {ci.name for ci in reduced.values()}
        keep = needed & set(meta0.fetches)
        fz = frozenset(keep) if keep else None
        if grouped is not None:
            hit = grouped._pruned.get(fz)
            if hit is not None:
                return _DEFAULT.aggregate(
                    program, GroupedFrame(hit, keys)
                )
            if grouped._agg_count >= 1:
                # second aggregate with a NEW read set: one full
                # materialisation (memoized on the node) serves this
                # and every later aggregate/frame() for free
                mat = self._materialize(needed_hint=needed)
                grouped._agg_count += 1
                return _DEFAULT.aggregate(
                    program, GroupedFrame(mat, keys)
                )
        mat = self._materialize(
            needed_hint=needed,
            count_use=False,
            keep=keep or None,
        )
        # the counter tracks ACTUAL fetch pruning: keep applies only to
        # a fused tail group dispatched pooled/affinity — a lone eager
        # stage always computes its full fetch set, and the fused-
        # SERIAL leg runs the eager per-stage chain (keep ignored)
        if keep and any(
            r.get("fused", 0) >= 2
            and r.get("dispatch") in ("pool", "affinity")
            for r in self._last_records
        ):
            observability.note_plan_fused_reduce()
        if grouped is not None:
            grouped._pruned[fz] = mat
            grouped._agg_count += 1
        return _DEFAULT.aggregate(program, GroupedFrame(mat, keys))

    # -- surface -------------------------------------------------------------

    @property
    def is_materialized(self) -> bool:
        return self._materialized is not None

    def warmup(self) -> List[str]:
        """Prime the fused-chain executables this plan will actually
        dispatch — bucketed sizes, donating entries, every pool device —
        without executing the plan (:func:`warm_plan`)."""
        return warm_plan(self)

    def explain_plan(self) -> str:
        return explain_plan(self)

    def explain_analyze(self) -> str:
        """Execute the plan under a request ledger and render the
        measured report (``tfs.explain(frame, analyze=True)``)."""
        return explain_analyze(self)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._materialize(count_use=False), name)

    def __repr__(self):
        return self.explain_plan()


class _SchemaShim:
    """Schema-only stand-in for a chain's (never materialised) output
    frame — exactly the surface the engine's reduce/aggregate setup and
    validation read: ``schema``, ``num_rows``, ``block_sizes``.  Derived
    chain outputs shadow same-named source columns; untouched source
    columns pass through (the non-trimmed chain contract).  A TRIMMED
    chain drops every passthrough, so its shim carries ONLY the derived
    columns — merging entry columns would falsely validate keys the
    real output frame will not have."""

    __slots__ = ("schema", "num_rows", "block_sizes")

    def __init__(
        self,
        entry: TensorFrame,
        final_infos: Mapping[str, ColumnInfo],
        trim: bool = False,
    ):
        cols: Dict[str, ColumnInfo] = (
            {} if trim else {ci.name: ci for ci in entry.schema}
        )
        cols.update(final_infos)
        self.schema = Schema(list(cols.values()))
        self.num_rows = entry.num_rows
        self.block_sizes = list(entry.block_sizes)


class LazyGroupedFrame(GroupedFrame):
    """``lazy.group_by(...)`` over an unmaterialised plan: the grouping
    is deferred to ``aggregate``, which knows its read set and prunes
    the chain's fetches to exactly keys + reduced columns
    (:meth:`LazyFrame._aggregate_terminal`).  Accessing ``.frame``
    materialises the full plan (the eager escape hatch)."""

    def __init__(self, lazy: "LazyFrame", keys: Sequence[str]):
        if not keys:
            raise ValidationError("group_by needs at least one key column")
        self.lazy = lazy
        self.keys = list(keys)
        # materialize-once across repeat aggregates: pruned results per
        # read set, and the count that flips to full materialisation
        self._pruned: Dict[Optional[frozenset], TensorFrame] = {}
        self._agg_count = 0

    @property
    def frame(self) -> TensorFrame:
        return self.lazy._materialize(count_use=False)


def _release_cache(cache) -> None:
    """``weakref.finalize`` body for planner-created caches: drop the
    shards and refund the HBM budget at frame GC."""
    cache.release()


def _first_step_cols(chain: Sequence[LazyFrame]) -> Optional[Set[str]]:
    if not chain:
        return None
    st = chain[0]._step
    return {st.program.column_for_input(n) for n in st.program.input_names}


def _reduce_cols(program: Program) -> Set[str]:
    """Frame columns a reduce program will consume — the auto-cache
    hint.  Feed-dict renames resolve to the fed column; unrenamed inputs
    strip the reduce suffix (``x_input`` / ``x_1`` / ``x_2`` -> ``x``)."""
    cols: Set[str] = set()
    for n in program.input_names:
        col = program.column_for_input(n)
        if col != n:
            cols.add(col)
            continue
        for suf in ("_input", "_1", "_2"):
            if n.endswith(suf):
                cols.add(n[: -len(suf)])
                break
        else:
            cols.add(n)
    return cols


# ---------------------------------------------------------------------------
# group dispatch
# ---------------------------------------------------------------------------


def _flush(
    nodes: List[LazyFrame],
    frame: TensorFrame,
    records: List[Dict],
    start_idx: int,
    keep: Optional[Set[str]] = None,
) -> TensorFrame:
    """Execute ``nodes``' steps over ``frame``: maximal fusable runs
    dispatch as ONE chained pass; everything else (host-staged,
    ragged-input, lone stages) runs the plain eager verb — the same
    dispatch the eager path would make.  ``keep`` prunes the fetches of
    a fused group that ENDS the segment (terminal consumers)."""
    i = 0
    while i < len(nodes):
        steps = [nd._step for nd in nodes[i:]]
        n, why, _ = _fusable_run(steps, _device_infos(frame))
        if n >= 2:
            frame = _dispatch_fused(
                nodes[i : i + n],
                frame,
                records,
                start_idx + i,
                keep=keep if i + n == len(nodes) else None,
            )
            i += n
        else:
            frame = _dispatch_single(
                nodes[i],
                frame,
                records,
                start_idx + i,
                why if n == 0 else "single_stage",
            )
            i += 1
    return frame


def _measured(fn, rows: int) -> Tuple[Any, Dict[str, Any]]:
    """Run ``fn()`` and return ``(result, measurement)`` — wall time and
    the resource deltas every plan record carries (round 15: the
    substance behind ``tfs.explain(frame, analyze=True)``).

    Metered through a nested :class:`observability.RequestLedger`, NOT
    a global counters-delta window: the ledger is exact per thread
    (staging lanes inherit the context), so a concurrent request in the
    same process cannot contaminate a stage's h2d/trace attribution.
    The ledger is deliberately never ``finish()``-ed — internal stage
    metering must not fold into the per-tenant request aggregates or
    the slow-request log (an enclosing bridge request's ledger still
    sees every delta via parent chaining)."""
    led = observability.RequestLedger(method="plan_stage")
    token = observability.activate_request(led)
    t0 = time.perf_counter()
    try:
        out = fn()
    finally:
        observability.deactivate_request(token)
    wall = time.perf_counter() - t0
    c = led.snapshot()["counters"]
    m: Dict[str, Any] = {
        "wall_s": round(wall, 6),
        "h2d_bytes": c.get("h2d_bytes_staged", 0),
        "traces": c.get("program_traces", 0),
        "rows": rows,
        "rows_per_s": round(rows / wall, 1) if wall > 0 else None,
    }
    if c.get("pool_blocks"):
        m["pool_blocks"] = c["pool_blocks"]
    if c.get("cache_shard_hits"):
        m["shard_hits"] = c["cache_shard_hits"]
    if c.get("block_retries"):
        m["retries"] = c["block_retries"]
    return out, m


def _dispatch_single(
    node: LazyFrame,
    frame: TensorFrame,
    records: List[Dict],
    idx: int,
    reason: str,
) -> TensorFrame:
    st = node._step

    def run():
        if st.kind == "map_rows":
            return _DEFAULT.map_rows(
                st.program, frame, host_stage=st.host_stage
            )
        return _DEFAULT.map_blocks(
            st.program, frame, trim=st.trim, host_stage=st.host_stage
        )

    out, measured = _measured(run, frame.num_rows)
    node._runs += 1
    records.append(
        {
            "stage": idx,
            "verb": st.label,
            "fused": 1,
            "dispatch": "eager",
            "reason": reason,
            **measured,
        }
    )
    return out


def _dispatch_fused(
    group: List[LazyFrame],
    frame: TensorFrame,
    records: List[Dict],
    idx: int,
    keep: Optional[Set[str]] = None,
) -> TensorFrame:
    steps = [nd._step for nd in group]
    try:
        meta = _compose(steps, frame, keep=keep)
    except ValidationError:
        if keep is None:
            raise
        # the terminal reads no derived column: nothing to prune
        meta = _compose(steps, frame)
    warm = any(nd._runs > 0 for nd in group) or _chain_warm(steps)
    rec = _choose_dispatch(meta, frame, warm)
    decision = rec.pop("decision")
    reason = rec.pop("reason")
    if decision in ("pool", "affinity") and frame.num_rows > 0:
        cache = frame_cache.active_cache(frame)
        devices = (
            cache.devices if cache is not None else device_pool.pool_devices()
        )
        (out, run_rec), measured = _measured(
            lambda: _run_pooled_chain(meta, frame, cache, devices),
            frame.num_rows,
        )
        rec.update(run_rec)
        # the observed payoff of the pool decision: measured per-device
        # occupancy collapses to an effective-parallelism scalar the
        # analyze rendering reports next to the decision's reason
        occ = run_rec.get("device_pool", {}).get("occupancy")
        if occ:
            measured["effective_parallelism"] = round(sum(occ), 2)
    else:
        out, measured = _measured(
            lambda: _run_serial_chain(steps, frame), frame.num_rows
        )
    rec.update(measured)
    # measured-throughput feedback (TFS_PLAN_CALIBRATE reads it back
    # through _choose_dispatch on the next identical chain)
    _calib_note(meta, frame, decision, measured.get("rows_per_s"))
    observability.note_plan_fused_dispatch()
    if meta.pruned:
        observability.note_plan_columns_pruned(len(meta.pruned))
    records.append(
        {
            "stage": idx,
            "verb": "+".join(st.label for st in steps),
            "fused": len(group),
            "dispatch": decision,
            "reason": reason,
            "pruned": list(meta.pruned),
            **rec,
        }
    )
    for nd in group:
        nd._runs += 1
    return out


# ---------------------------------------------------------------------------
# routing + explain
# ---------------------------------------------------------------------------


def root_for(frame: TensorFrame) -> LazyFrame:
    """The ONE shared plan root for a TensorFrame object (get-or-create)
    — used by both ``frame.lazy()`` and the ``TFS_PLAN`` routing, so
    chains built from either entry count as consumers of the same
    subplan (the auto-cache trigger).  Locked: two concurrent bridge
    requests racing the create would otherwise each get a root and
    split the consumer counting."""
    root = getattr(frame, "_tfs_lazy_root", None)
    if root is None:
        with LazyFrame._TREE_LOCK:
            root = getattr(frame, "_tfs_lazy_root", None)
            if root is None:
                root = LazyFrame(source=frame)
                frame._tfs_lazy_root = root
    return root


def maybe_lazy(frame) -> Optional[LazyFrame]:
    """The LazyFrame a module-level map verb should append to, or None
    for the eager path: the frame is already lazy, or ``TFS_PLAN`` is on
    and the frame is a plain TensorFrame."""
    if isinstance(frame, LazyFrame):
        return frame
    if planning_enabled() and isinstance(frame, TensorFrame):
        return root_for(frame)
    return None


def ensure_frame(frame):
    """A concrete TensorFrame for surfaces that cannot stay lazy
    (pipelines, warmup, the bridge)."""
    if isinstance(frame, LazyFrame):
        return frame._materialize(count_use=False)
    return frame


# ---------------------------------------------------------------------------
# plan warmup (round 19 satellite: the fused-chain bucket grid)
# ---------------------------------------------------------------------------


def warm_plan(frame: "LazyFrame") -> List[str]:
    """Prime the executables the optimizer will ACTUALLY dispatch for
    this plan, without executing it.

    ``Executor.warmup`` primes one program's own entries, but a planned
    chain dispatches each stage through the engine's DONATING entries at
    BUCKETED sizes on every pool device — different jit-cache keys, so a
    per-stage warmup still left the first planned run compiling.  This
    walks the pending chain, composes the fused groups, and zeros-
    executes the exact ``_apply_stages`` path once per (bucketed size,
    device) with trace counting suppressed (programs are pure by
    contract), seeding the jit caches — and, with ``TFS_COMPILE_CACHE``
    configured, the persistent cache — the first real dispatch will hit.
    The roofline probe and the bucket-pad proofs are primed too, so the
    pool-vs-serial decision costs nothing at dispatch.  Returns the
    primed (rows x devices) grid labels."""
    import jax

    if not isinstance(frame, LazyFrame):
        raise ValidationError("warm_plan: takes a LazyFrame")
    chain: List[LazyFrame] = []
    cur = frame
    while cur._materialized is None:
        chain.append(cur)
        cur = cur._parent
    chain.reverse()
    src = cur._materialized
    if src is None or not chain or src.num_rows == 0:
        return []
    steps = [nd._step for nd in chain]
    n, _, _ = _fusable_run(steps, _device_infos(src))
    if n < 2:
        st = steps[0]
        if st.stage_bound or st.kind not in ("map_blocks", "map_rows"):
            return []
        fps = _DEFAULT.warmup(
            st.program,
            src,
            rows_level=st.kind == "map_rows",
            host_stage=st.host_stage,
        )
        return list(fps)
    meta = _compose(steps[:n], src)
    pads = _chain_pads(meta, src)
    sizes = src.block_sizes
    exec_sizes = sorted(
        {
            pads[bi] if pads[bi] is not None else s
            for bi, s in enumerate(sizes)
            if s > 0
        }
    )
    if not exec_sizes:
        return []
    cache = frame_cache.active_cache(src)
    if cache is not None:
        devs = [cache.devices[di] for di in sorted(set(cache.assignment))]
    else:
        devs = list(device_pool.pool_devices()) or [None]
    # prime the cost probe so the first dispatch's pool/serial decision
    # is a cache hit instead of a compile
    _fused_intensity(meta.program, src)
    donate_entries = cache is None
    # real sizes each bucket serves: the dispatch slices pads back off,
    # and that slice is its own (per-device) executable to prime
    reals: Dict[int, Set[int]] = {}
    for bi, s in enumerate(sizes):
        if s > 0 and pads[bi] is not None:
            reals.setdefault(pads[bi], set()).add(s)
    primed: List[str] = []
    for n_rows in exec_sizes:
        zeros = {}
        for name in meta.src_inputs:
            col = src.column(name)
            cell = tuple(np.shape(col.data)[1:])
            st_ = dtypes.coerce(col.info.scalar_type)
            zeros[name] = np.zeros((n_rows,) + cell, st_.np_dtype)
        for dev in devs:
            staged = {
                k: jax.device_put(v, dev) for k, v in zeros.items()
            }
            with observability.suppress_trace_count():
                outs = _apply_stages(
                    meta, staged, donate_entries=donate_entries
                )
                for real in sorted(reals.get(n_rows, ())):
                    sliced = {k: v[:real] for k, v in outs.items()}
                    jax.block_until_ready(list(sliced.values()))
            jax.block_until_ready(outs)
            primed.append(
                f"chain[{len(meta.steps)}]x{n_rows}@"
                f"{getattr(dev, 'id', 'default')}"
            )
    return primed


# ---------------------------------------------------------------------------
# planner-aware multi-epoch driver (round 19)
# ---------------------------------------------------------------------------


def _prime_blocks(frame, cache, missing: List[int]) -> None:
    """Best-effort background re-staging of evicted entry shards
    between epochs: spill-backed shards restore from disk, plain shards
    re-stage from the authoritative host columns.  Any failure simply
    leaves the block for the dispatch path's inline re-staging."""
    import jax

    names = None
    for b in cache.blocks:
        if b is not None:
            names = list(b)
            break
    for bi in missing:
        try:
            if cache.shard(bi) is not None:  # spill restore / raced in
                continue
            if names is None:
                return
            dev = cache.devices[cache.assignment[bi]]
            lo, hi = frame.offsets[bi], frame.offsets[bi + 1]
            shard = {}
            for name in names:
                col = frame.column(name)
                a = np.asarray(col.data)[lo:hi]
                st_ = dtypes.coerce(col.info.scalar_type)
                if a.dtype != st_.np_dtype:
                    a = a.astype(st_.np_dtype)
                observability.note_h2d_bytes(a.nbytes)
                shard[name] = jax.device_put(a, dev)
            if not cache.insert(bi, shard):
                return  # budget full: stop, dispatch re-stages inline
        except Exception:  # noqa: BLE001 — priming must never fail a run
            return


def _start_epoch_primer(root: "LazyFrame"):
    mat = root._materialized
    if mat is None:
        return None
    cache = frame_cache.active_cache(mat)
    if cache is None:
        return None
    missing = [bi for bi, b in enumerate(cache.blocks) if b is None]
    if not missing:
        return None
    t = threading.Thread(
        target=_prime_blocks,
        args=(mat, cache, missing),
        daemon=True,
        name="tfs-plan-epoch-primer",
    )
    t.start()
    return t


def iterate_epochs(
    frame, step, epochs: int, job_id: Optional[str] = None
) -> List[Any]:
    """Planner-aware multi-epoch driver (``tfs.iterate_epochs``): run
    ``step(lazy_frame, epoch)`` ``epochs`` times over one shared plan
    root.

    The planner knows the loop shape up front, so it does what the
    round-14 heuristics only discovered mid-loop: the entry frame's
    sharded cache inserts on the FIRST consumption (not the second), so
    epoch 1 onwards reads resident shards — 0 steady-state H2D — and
    between epochs a background primer re-stages any shards the
    ``TFS_HBM_BUDGET`` LRU evicted, through the same staging path, so
    epoch N+1's blocks are resident while epoch N's host work (loss
    handling, param updates) runs.  Steady-state epochs re-trace
    nothing: the chain's executables and fusion metadata are shared
    across epochs.

    ``step`` receives the shared :class:`LazyFrame` root and the epoch
    index; derive chains and reduce/aggregate off it exactly as in a
    hand-written loop (params may change between epochs via
    ``update_params`` — the plan re-executes, the executables stay
    warm).  Returns the per-epoch results.

    ``job_id`` (round 20) makes the loop durable: each epoch's result
    (npz-serializable pytrees — arrays, scalars, nested containers) is
    journaled at the epoch boundary, a resumed loop replays journaled
    epochs' results WITHOUT running ``step`` for them, and a completed
    loop returns its journaled result list exactly once.  ``step`` must
    derive any carried state (params it updates) from the journaled
    results, not from process-local mutation, for the resumed epochs to
    be bit-identical — the epoch-matrix test pins exactly this shape."""
    if epochs < 1:
        raise ValidationError("iterate_epochs: epochs must be >= 1")
    if isinstance(frame, LazyFrame):
        root = frame
    elif isinstance(frame, TensorFrame):
        root = root_for(frame)
    else:
        raise ValidationError(
            "iterate_epochs: takes a TensorFrame or LazyFrame"
        )
    writer = None
    start_epoch = 0
    results: List[Any] = []
    if job_id is not None:
        from .. import recovery

        writer = recovery.adopt(
            job_id,
            "iterate_epochs",
            recovery.job_fingerprint("iterate_epochs", epochs=epochs),
        )
        # completed AND interrupted loops replay journaled epochs from
        # their per-boundary states (kept past complete for this); a
        # torn-state raise here must release the in-process job slot
        with recovery.durable.closing_on_error(writer):
            start_epoch = min(writer.boundary, epochs)
            for e in range(start_epoch):
                results.append(
                    recovery.unpack_tree(
                        writer.load_state(e) or {}, writer.extras()[e]
                    )
                )
                # the epoch analog of a skipped stream window:
                # journaled, replayed, never re-executed
                observability.note_journal_window_skipped()
        if writer.completed:
            writer.close()
            return results
    if epochs >= 2 and root._materialized is not None:
        # declare the loop's >= 2 consumptions up front: the entry
        # auto-cache triggers on the FIRST consumption instead of
        # waiting to observe a second one
        root._mat_uses = max(root._mat_uses, 1)
    primer = None
    try:
        for e in range(start_epoch, epochs):
            cancellation.checkpoint()  # epoch boundary
            results.append(step(root, e))
            if writer is not None:
                from .. import recovery

                arrays, extra = recovery.pack_tree(results[-1])
                writer.append(arrays=arrays, extra=extra)
            # the primer runs CONCURRENTLY with the next epoch (the
            # overlap is the point: re-staging evicted shards rides
            # under epoch N+1's host work; the dispatch path tolerates
            # racing best-effort inserts — worst case a block re-stages
            # inline exactly as it would have without the primer).  At
            # most one primer is in flight.
            if e + 1 < epochs and (primer is None or not primer.is_alive()):
                primer = _start_epoch_primer(root)
    except BaseException:
        if writer is not None:
            writer.close()  # stays resumable from the journal
        raise
    finally:
        if primer is not None:
            primer.join()
    if writer is not None:
        from .. import recovery

        with recovery.durable.closing_on_error(writer):
            writer.complete(keep_states=True)
    return results


# ---------------------------------------------------------------------------
# per-window plans for the streaming verbs (round 19)
# ---------------------------------------------------------------------------


def run_window_chain(
    frame: TensorFrame, steps: Sequence[Tuple[str, Program, bool]]
) -> TensorFrame:
    """Execute a stacked map chain over ONE streaming window through
    plan construction: fusion, dead-column pruning, and the static
    ``analysis.rows_independent`` bucket pads all apply, and the fusion
    metadata / executables are shared across windows (the stage
    Programs are the cache keys).  The CSE registry is bypassed —
    window frames never repeat.  Bit-identical to dispatching the
    stages eagerly per window: the fused chain applies each stage's own
    compiled entry."""
    lz = LazyFrame(source=frame)
    cur = lz
    for kind, program, trim in steps:
        cur = cur._append(kind, program, trim=trim)
    out = cur._materialize(count_use=False, cse=False)
    observability.note_plan_stream_window()
    return out


def explain_plan(frame: LazyFrame) -> str:
    """Render the optimized logical plan WITHOUT executing it: stage
    list, fused groups (computed by the same grouping walk the executor
    uses), pruned columns, cache-insertion barriers, and — after a run —
    the recorded per-group pool/serial decisions."""
    chain: List[LazyFrame] = []
    cur = frame
    while cur._step is not None:
        chain.append(cur)
        cur = cur._parent
    chain.reverse()
    src = cur._materialized if cur._materialized is not None else cur._source
    lines = ["== logical plan (lazy) =="]
    lines.append(
        f"source: {src.num_rows} rows x {len(src.columns)} cols x "
        f"{src.num_blocks} block(s) [{', '.join(src.column_names)}]"
    )
    if not chain:
        lines.append("(no stages: materialises to the source frame)")
        return "\n".join(lines)

    # dry-run grouping: mirror _flush, but threading the statically
    # inferred visible columns instead of executing.  Barriers (>= 2
    # consumers) bound fusion exactly like the executor's flush points;
    # an unfusable host-staged stage makes the schema opaque downstream.
    gid_of: Dict[int, Tuple[Optional[int], Optional[str]]] = {}
    visible: Optional[Dict[str, ColumnInfo]] = _device_infos(src)
    consumed: Set[str] = set()
    barrier_idx = {k for k, nd in enumerate(chain) if nd._children >= 2}
    gid = 0
    i = 0
    while i < len(chain):
        stop = next((b for b in sorted(barrier_idx) if b >= i), None)
        seg_end = len(chain) if stop is None else stop + 1
        steps = [nd._step for nd in chain[i:seg_end]]
        if visible is None:
            n, why, after = 0, "schema opaque after host stage", None
        else:
            n, why, after = _fusable_run(steps, visible)
        if n >= 2:
            for k in range(i, i + n):
                gid_of[k] = (gid, None)
            gid += 1
            visible = after if n == len(steps) else None
            i += n
        else:
            gid_of[i] = (None, why if n == 0 else "single_stage")
            visible = None if n == 0 else after
            i += 1
    for k, nd in enumerate(chain):
        st = nd._step
        g, why = gid_of[k]
        cols = ", ".join(
            dict.fromkeys(
                st.program.column_for_input(n)
                for n in st.program.input_names
            )
        )
        consumed.update(
            st.program.column_for_input(n) for n in st.program.input_names
        )
        tag = f"fused group {g}" if g is not None else f"eager ({why})"
        mark = (
            "  [barrier: >=2 consumers -> auto-cache]"
            if k in barrier_idx
            else ""
        )
        lines.append(
            f" stage {k:<2} {st.label:<20} reads [{cols}]  {tag}{mark}"
        )
    dead = sorted(set(_device_infos(src)) - consumed)
    lines.append(
        "pruned columns (never staged by fused groups): "
        + (", ".join(dead) if dead else "none")
    )
    inserted = [
        f"stage {k} (inserted)"
        for k, nd in enumerate(chain)
        if nd._auto_cached
    ]
    pendings = [
        f"stage {k} ({chain[k]._children} consumers)"
        for k in sorted(barrier_idx)
        if not chain[k]._auto_cached
    ]
    lines.append(
        "cache insertions: "
        + (", ".join(inserted + pendings) if (inserted or pendings) else "none")
    )
    recs = frame._last_records
    if recs:
        lines.append("last run:")
        for r in recs:
            extra = ""
            if r.get("intensity_flops_per_byte") is not None:
                extra = f", intensity={r['intensity_flops_per_byte']}"
            lines.append(
                f"  stage {r['stage']}: {r['verb']} -> {r['dispatch']} "
                f"(reason={r['reason']}{extra})"
            )
    return "\n".join(lines)


def _render_analyze(frame: LazyFrame, executed_now: bool) -> str:
    """The measured half of ``explain(analyze=True)``: per-group wall
    time, bytes staged, pool occupancy, and the pool-vs-serial decision
    with its observed payoff — rendered from the per-stage measurements
    every plan execution records."""
    recs = frame._last_records
    lines = ["== analyze (measured) =="]
    if not executed_now:
        lines.append(
            "(plan was already materialized; measurements are from its "
            "last execution)"
        )
    if not recs:
        lines.append("(no recorded execution — the plan has no stages)")
    tot_wall = 0.0
    tot_h2d = 0
    for r in recs:
        wall = r.get("wall_s")
        tot_wall += wall or 0.0
        tot_h2d += r.get("h2d_bytes") or 0
        head = (
            f" group stage {r['stage']}: {r['verb']} "
            f"[{'fused x' + str(r['fused']) if r.get('fused', 1) >= 2 else 'eager'}]"
        )
        lines.append(head)
        lines.append(
            f"   dispatch={r.get('dispatch')} (reason={r.get('reason')})"
            + (
                f" intensity={r['intensity_flops_per_byte']}"
                if r.get("intensity_flops_per_byte") is not None
                else ""
            )
        )
        lines.append(
            f"   wall={wall}s  h2d_bytes={r.get('h2d_bytes')}  "
            f"traces={r.get('traces')}  rows/s={r.get('rows_per_s')}"
        )
        dp = r.get("device_pool")
        if dp:
            payoff = r.get("effective_parallelism")
            lines.append(
                f"   pool: blocks={dp.get('blocks_per_device')} "
                f"occupancy={dp.get('occupancy')}"
                + (
                    f" -> observed payoff: {payoff}x effective "
                    f"parallelism across {dp.get('devices')} device(s)"
                    if payoff is not None
                    else ""
                )
            )
        if r.get("retries"):
            lines.append(f"   retries={r['retries']}")
        if r.get("pruned"):
            lines.append(f"   pruned={r['pruned']}")
    lines.append(
        f" totals: wall={round(tot_wall, 6)}s  h2d_bytes={tot_h2d}"
    )
    led = getattr(frame, "_last_ledger", None)
    if led:
        c = led.get("counters", {})
        lines.append(
            f" request: cid={led.get('correlation_id')} "
            f"wall={led.get('wall_s')}s "
            f"h2d={c.get('h2d_bytes_staged', 0)} "
            f"traces={c.get('program_traces', 0)} "
            f"retries={c.get('block_retries', 0)} "
            f"blocks_per_device={led.get('blocks_per_device')}"
        )
    return "\n".join(lines)


def explain_analyze(frame: LazyFrame) -> str:
    """``EXPLAIN ANALYZE`` for a planned frame: execute the plan under a
    :func:`observability.request_ledger` (nesting safely inside any
    active bridge request's ledger) and render the logical plan PLUS the
    measured per-stage/per-group report — wall time, bytes staged, pool
    occupancy, and each pool-vs-serial decision with its observed
    payoff.  A plan that already materialized renders its last
    execution's measurements (plans memoize; re-deriving the chain from
    the source re-executes)."""
    executed_now = frame._materialized is None
    with observability.request_ledger(method="explain_analyze") as led:
        frame._materialize(count_use=False)
    if executed_now:
        frame._last_ledger = led.snapshot()
    return (
        explain_plan(frame)
        + "\n"
        + _render_analyze(frame, executed_now)
    )
