"""Program: the named-input tensor program fed to every verb.

TPU-native re-design of the reference's graph layer (L4): where the reference
ships a serialized TF ``GraphDef`` whose ``Placeholder`` nodes are named after
DataFrame columns (``TensorFlowOps.scala:101-141``), a ``Program`` here wraps a
*jax-traceable function* whose argument names are the input names and whose
outputs are named fetches.  Under ``jit`` the function is traced once per input
signature and compiled by XLA — the compiled executable plays the role of the
broadcast graph bytes (SURVEY.md §2.7 P6: program broadcast == jit cache).

Three construction paths, mirroring the reference's three graph sources:
python function (== python TF graph), the DSL (``tensorframes_tpu.dsl``), and
frozen ``GraphDef`` import (``tensorframes_tpu.graphdef``) — the latter two
both produce a plain traceable function and land here.

``analyze_program`` is the analog of ``TensorFlowOps.analyzeGraphTF``
(``TensorFlowOps.scala:101-141``): it runs shape inference (``jax.eval_shape``
— no FLOPs, no device) over declared input specs and returns a
``GraphNodeSummary`` per input/output, with user hints overriding inferred
shapes exactly like the reference's ``ShapeDescription`` override
(``TensorFlowOps.scala:126-133``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
import threading
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes, observability
from .dtypes import ScalarType
from .schema import SchemaError
from .shape import Shape, UNKNOWN


class ProgramError(ValueError):
    """Raised for malformed programs (bad signature, bad outputs, bad hints)."""


@dataclasses.dataclass(frozen=True)
class GraphNodeSummary:
    """Shape/dtype summary of one program input or output.

    Mirrors ``GraphNodeSummary`` (``TensorFlowOps.scala:163-169``)."""

    name: str
    is_input: bool
    is_output: bool
    scalar_type: ScalarType
    shape: Shape

    def __repr__(self):
        role = "input" if self.is_input else "output"
        return f"{self.name}[{role}]: {self.scalar_type}{self.shape}"


def device_of(array):
    """The one device ``array`` lies on; None for a host array, a tracer
    or an array laid out over several devices."""
    sharding = getattr(array, "sharding", None)
    if sharding is None or len(sharding.device_set) != 1:
        return None
    (dev,) = sharding.device_set
    return dev


def _committed_device(args):
    """The one device the array arguments of a call are committed to.

    jax runs a computation where its committed arguments live and moves
    every uncommitted argument there AT EVERY CALL.  None when nothing
    is committed (host arrays, fresh ``jnp`` values: the call runs on
    the default device), when an argument spans several devices (the
    mesh / ``shard_map`` entries) or when two arguments disagree (jax
    raises on those itself)."""
    found = None
    for leaf in jax.tree_util.tree_leaves(args):
        # tracers and host arrays have no ``committed``
        if not getattr(leaf, "committed", False):
            continue
        dev = device_of(leaf)
        if dev is None or (found is not None and dev != found):
            return None
        found = dev
    return found


class _Residency:
    """Where ONE state of a program's params lives: the originals on
    their ``home`` device and, made on demand, one committed replica on
    each other device a block of the program ran on.

    Keyed on the identity of the ``_params`` values it was made from
    (kept in ``params``): ``update_params`` and the planner's direct write
    (``_sync_probe_params``) both replace those objects, so a replica
    of an older state is never handed out — :meth:`current` fails and
    the program starts a new residency.  A replica is the params' bytes
    once a device; it is freed with this object, i.e. with the program
    or at the next params state."""

    __slots__ = ("params", "home", "spread", "replicas", "_lock")

    def __init__(self, params: Mapping[str, Any]):
        self.params = dict(params)
        devs = set()
        self.spread = False
        for leaf in jax.tree_util.tree_leaves(self.params):
            on = leaf.sharding.device_set
            # params laid out over a mesh are the mesh entries' business
            self.spread = self.spread or len(on) != 1
            devs |= on
        self.home = next(iter(devs)) if len(devs) == 1 else None
        self.replicas: Dict[Any, Dict[str, Any]] = {}
        self._lock = threading.Lock()

    def current(self, params: Mapping[str, Any]) -> bool:
        return all(
            a is b for a, b in zip(self.params.values(), params.values())
        )

    def resident(self, device) -> bool:
        return device == self.home or device in self.replicas

    def replica(self, device) -> Dict[str, Any]:
        """The params committed to ``device``, placed by the first call."""
        rep = self.replicas.get(device)
        if rep is None:
            with self._lock:
                rep = self.replicas.get(device)
                if rep is None:
                    moved = sum(
                        leaf.nbytes
                        for leaf in jax.tree_util.tree_leaves(self.params)
                        if device not in leaf.sharding.device_set
                    )
                    # times the call: the copies land after it returns,
                    # under the first dispatch that reads them
                    with observability.span(
                        "program.place_params", "programs", bytes=moved
                    ):
                        rep = jax.device_put(self.params, device)
                    observability.note_params_placed(moved)
                    self.replicas[device] = rep
        return rep


def deserialize_program(data: bytes) -> "Program":
    """Rehydrate a :meth:`Program.serialize` artifact.

    The artifact is self-contained (params frozen in, shapes possibly
    symbolic): the deserialized program runs on any backend jax supports,
    the way the reference's broadcast graph bytes run in any executor.
    Block-level semantics only — the frozen executable cannot be re-vmapped,
    so feed it to ``map_blocks``/``reduce_*``, not ``map_rows``."""
    import json

    from jax import export as jexp

    sep = data.index(b"\x00")
    header = json.loads(data[:sep].decode())
    if header.get("format") != "tfs-program-v1":
        raise ProgramError(
            f"not a serialized tensorframes program (format="
            f"{header.get('format')!r})"
        )
    exported = jexp.deserialize(data[sep + 1 :])
    input_names = header["inputs"]

    def fn(**kwargs):
        return exported.call({n: kwargs[n] for n in input_names})

    return Program(
        fn, input_names, header["fetches"], header.get("feed") or None
    )


class Program:
    """A tensor program with named inputs and named outputs.

    ``fn`` takes keyword arrays named by ``input_names`` and returns either a
    ``dict`` of named outputs, a single array (allowed only when ``fetches``
    names exactly one output), or a tuple matching ``fetches``.  Outputs are
    canonically ordered sorted-by-name, matching the reference's output schema
    ordering (``DebugRowOps.scala:349-372``).

    ``feed_dict`` maps input name -> frame column name, the reference's
    ``map_rows`` feed-dict contract (``core.py:175-211``,
    ``PythonInterface.scala:120-127``).
    """

    def __init__(
        self,
        fn: Callable[..., Any],
        input_names: Sequence[str],
        fetches: Optional[Sequence[str]] = None,
        feed_dict: Optional[Mapping[str, str]] = None,
        params: Optional[Mapping[str, Any]] = None,
    ):
        self._fn = fn
        self._declared_fetches = list(fetches) if fetches is not None else None
        all_names = list(input_names)
        # a param value may be a single array OR a pytree of arrays (a model
        # parameter tree) — both flow through jit as traced arguments
        self._params: Dict[str, Any] = {
            k: jax.tree_util.tree_map(jnp.asarray, v)
            for k, v in (params or {}).items()
        }
        # monotonic params generation: bumped by update_params so caches
        # keyed on live param VALUES (the planner's cross-plan CSE
        # registry) can tell two states of one Program apart without
        # holding or hashing the arrays themselves
        self._params_version = 0
        # where the current params state lives, device by device
        # (_Residency); None until a call with committed inputs asks
        self._residency: Optional[_Residency] = None
        for k in self._params:
            if k not in all_names:
                raise ProgramError(
                    f"params key {k!r} is not a program argument; "
                    f"arguments are {all_names}"
                )
        # column-fed inputs exclude param-fed arguments
        self._input_names = [n for n in all_names if n not in self._params]
        if not self._input_names:
            raise ProgramError(
                "a program needs at least one column-fed input (all "
                "arguments were bound by params)"
            )
        self._feed = dict(feed_dict or {})
        for k in self._feed:
            if k not in self._input_names:
                raise ProgramError(
                    f"feed_dict key {k!r} is not a program input; "
                    f"inputs are {self._input_names}"
                )
        self._fetches: Optional[List[str]] = None  # resolved at first trace
        self._jitted = None
        self._jit_raw_obj = None
        self._vmapped = None
        self._vmap_raw_obj = None
        self._derived: Dict[Any, Any] = {}
        # output name -> Shape hint (ShapeDescription.scala:3-16); applied by
        # analyze() as a refinement and checked by the verbs at run time
        self._shape_hints: Dict[str, Shape] = {}
        # input name -> host preprocessing fn the engine merges into each
        # verb's host_stage (set by the GraphDef importer for in-graph
        # Decode* nodes; an explicit caller host_stage wins per input)
        self.host_prelude: Dict[str, Any] = {}

    # -- construction --------------------------------------------------------

    @staticmethod
    def wrap(
        fn_or_program,
        fetches: Optional[Sequence[str]] = None,
        feed_dict: Optional[Mapping[str, str]] = None,
        params: Optional[Mapping[str, Any]] = None,
    ) -> "Program":
        if isinstance(fn_or_program, Program):
            if params:
                raise ProgramError(
                    "cannot bind params on an existing Program; pass params "
                    "when the program is created, or call update_params"
                )
            if fetches is not None and sorted(fetches) != sorted(
                fn_or_program._declared_fetches or []
            ):
                raise ProgramError(
                    "cannot re-declare fetches on an existing Program; pass "
                    "fetches when the program is created/imported"
                )
            if feed_dict:
                return fn_or_program.with_feed(feed_dict)
            return fn_or_program
        # DSL nodes (and sequences of them) lower to a Program
        is_node = hasattr(fn_or_program, "to_program")
        is_node_seq = (
            isinstance(fn_or_program, (list, tuple))
            and fn_or_program
            and all(hasattr(x, "to_program") for x in fn_or_program)
        )
        if is_node or is_node_seq:
            if params:
                raise ProgramError(
                    "params are not supported for DSL-node programs; use "
                    "dsl.constant for fixed values or a python-function "
                    "program for updatable params"
                )
            from . import dsl  # local import: dsl depends on this module

            nodes = [fn_or_program] if is_node else list(fn_or_program)
            p = dsl.build_program(nodes, feed_dict=feed_dict)
            if fetches is not None and sorted(fetches) != sorted(
                p._declared_fetches or []
            ):
                raise ProgramError(
                    f"fetches {sorted(fetches)} do not match the DSL fetch "
                    f"node names {sorted(p._declared_fetches or [])}; name "
                    f"fetch nodes with .named(...) instead"
                )
            return p
        if not callable(fn_or_program):
            raise ProgramError(
                f"expected a callable, Program, or DSL node(s), got "
                f"{type(fn_or_program).__name__}"
            )
        sig = inspect.signature(fn_or_program)
        names = []
        for p in sig.parameters.values():
            if p.kind in (
                inspect.Parameter.POSITIONAL_OR_KEYWORD,
                inspect.Parameter.KEYWORD_ONLY,
            ):
                names.append(p.name)
            elif p.kind in (
                inspect.Parameter.VAR_POSITIONAL,
                inspect.Parameter.VAR_KEYWORD,
            ):
                raise ProgramError(
                    "program functions must declare explicit named parameters "
                    "(column names); *args/**kwargs are not allowed"
                )
        if not names:
            raise ProgramError("a program needs at least one named input")
        return Program(fn_or_program, names, fetches, feed_dict, params)

    def with_feed(self, feed_dict: Mapping[str, str]) -> "Program":
        """A copy with additional input->column renames merged in."""
        merged = dict(self._feed)
        merged.update(feed_dict)
        p = Program(
            self._fn,
            self._input_names + list(self._params),
            self._declared_fetches,
            merged,
            self._params,
        )
        p._shape_hints = dict(self._shape_hints)
        p.host_prelude = dict(self.host_prelude)
        return p

    def with_shape_hints(
        self, hints: Mapping[str, Sequence[int]]
    ) -> "Program":
        """A copy carrying output-shape hints (the reference's
        ``ShapeDescription`` override, ``TensorFlowOps.scala:126-133``):
        each hint refines — never contradicts — the engine-inferred shape.
        Applied by ``analyze`` and checked against real outputs by the map
        verbs."""
        p = Program(
            self._fn,
            self._input_names + list(self._params),
            self._declared_fetches,
            self._feed,
            self._params,
        )
        p._shape_hints = dict(self._shape_hints)
        p.host_prelude = dict(self.host_prelude)
        for name, s in hints.items():
            p._shape_hints[name] = Shape(s)
        if self._declared_fetches is not None:
            bad = sorted(set(p._shape_hints) - set(self._declared_fetches))
            if bad:
                raise ProgramError(
                    f"shape hints for unknown outputs {bad}; program "
                    f"outputs are {sorted(self._declared_fetches)}"
                )
        return p

    @property
    def shape_hints(self) -> Dict[str, Shape]:
        return dict(self._shape_hints)

    # -- accessors -----------------------------------------------------------

    @property
    def input_names(self) -> List[str]:
        """Column-fed input names (param-bound arguments excluded)."""
        return list(self._input_names)

    @property
    def param_names(self) -> List[str]:
        return list(self._params)

    @property
    def params(self) -> Dict[str, Any]:
        return dict(self._params)

    def update_params(self, **arrays) -> "Program":
        """Replace param values in place (shapes/dtypes must match).

        This is the iterative-driver contract: the reference re-embeds
        updated constants into a fresh graph every step
        (``kmeans_demo.py:68-80``, re-broadcast each iteration); here params
        are *traced arguments* of the compiled executable, so a shape-stable
        update reuses the jit cache — no re-trace, no re-compile, no
        re-broadcast."""
        # validate EVERY key before mutating anything: a mid-loop raise
        # must not leave _params half-updated at the old version — the
        # planner's CSE registry keys on (id, _params_version) and a
        # silent partial update would let it serve stale results
        validated: Dict[str, Any] = {}
        for k, v in arrays.items():
            if k not in self._params:
                raise ProgramError(
                    f"update_params: {k!r} is not a param; params are "
                    f"{sorted(self._params)}"
                )
            old = self._params[k]
            new = jax.tree_util.tree_map(jnp.asarray, v)
            old_leaves, old_def = jax.tree_util.tree_flatten(old)
            new_leaves, new_def = jax.tree_util.tree_flatten(new)
            if old_def != new_def:
                raise ProgramError(
                    f"update_params: {k!r} must keep its pytree structure "
                    f"(got {new_def}, expected {old_def}); structure "
                    f"changes force a re-compile — build a new Program"
                )
            for ol, nl in zip(old_leaves, new_leaves):
                if nl.shape != ol.shape or nl.dtype != ol.dtype:
                    raise ProgramError(
                        f"update_params: {k!r} must keep shape {ol.shape} /"
                        f" dtype {ol.dtype}, got {nl.shape} / {nl.dtype} "
                        f"(shape changes force a re-compile; build a new "
                        f"Program instead)"
                    )
            validated[k] = new
        self._params.update(validated)
        self._params_version += 1
        # the replicas of the old state go with it (a direct write past
        # this door cannot leave a stale one either: _Residency.current)
        self._residency = None
        return self

    def column_for_input(self, name: str) -> str:
        """Frame column feeding a given input (identity unless feed_dict)."""
        return self._feed.get(name, name)

    @property
    def columns_needed(self) -> List[str]:
        return [self.column_for_input(n) for n in self._input_names]

    @property
    def fetches(self) -> Optional[List[str]]:
        return list(self._fetches) if self._fetches is not None else (
            sorted(self._declared_fetches) if self._declared_fetches else None
        )

    # -- execution -----------------------------------------------------------

    def _normalize_outputs(self, out) -> Dict[str, Any]:
        if isinstance(out, dict):
            res = dict(out)
        elif isinstance(out, (tuple, list)):
            if self._declared_fetches is None or len(self._declared_fetches) != len(
                out
            ):
                raise ProgramError(
                    "tuple program outputs require fetches=[...] of matching "
                    f"length; got {len(out)} outputs, fetches="
                    f"{self._declared_fetches}"
                )
            res = dict(zip(self._declared_fetches, out))
        else:
            if self._declared_fetches is None or len(self._declared_fetches) != 1:
                raise ProgramError(
                    "a program returning a single array must declare exactly "
                    "one fetch name (pass fetches=['name']), or return a dict "
                    "{name: array}"
                )
            res = {self._declared_fetches[0]: out}
        if self._declared_fetches is not None:
            missing = [f for f in self._declared_fetches if f not in res]
            if missing:
                raise ProgramError(
                    f"program outputs {sorted(res)} are missing requested "
                    f"fetches {missing}"
                )
            res = {f: res[f] for f in self._declared_fetches}
        if not res:
            raise ProgramError("program produced no outputs")
        for name, v in res.items():
            if not isinstance(name, str):
                raise ProgramError(f"output names must be strings, got {name!r}")
            res[name] = jnp.asarray(v)
        # canonical order: sorted by name (DebugRowOps.scala:349-372)
        ordered = {k: res[k] for k in sorted(res)}
        if self._fetches is None:
            self._fetches = list(ordered)
        return ordered

    def call(
        self,
        inputs: Mapping[str, Any],
        params: Optional[Mapping[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Run the program (traceable; used inside jit/vmap/shard_map).

        ``params`` lets an enclosing jit pass the param values as *traced
        arguments*; when omitted, the current ``self._params`` are captured
        as trace-time constants (correct, but an enclosing jit built around
        such a call bakes the values in)."""
        if params is None:
            params = self._params
        # jit invokes the python function only on a signature-cache miss,
        # so each call here under tracing is one (re)trace of the user
        # program — the retrace counter the bench/tests assert against.
        # Analysis-time tracing (analyze/probes/export) is suppressed.
        observability.note_program_trace()
        kwargs = {n: inputs[n] for n in self._input_names}
        kwargs.update(params)
        return self._normalize_outputs(self._fn(**kwargs))

    def jitted(self):
        """The compiled entry: traced once per input shape/dtype signature.

        jax's jit cache is the broadcast mechanism (SURVEY.md P6): every block
        with the same signature reuses the same XLA executable, on any device.
        Params flow through as traced arguments, so ``update_params`` between
        calls reuses the compiled executable.
        """
        if self._jitted is None:
            self._jitted = self._bind_live_params(self._jit_raw())
        return self._jitted

    def _jit_raw(self):
        """The raw block-level jit object (``fn(inputs, params)``) —
        shared by :meth:`jitted` and the AOT ``lower().compile()`` path."""
        if getattr(self, "_jit_raw_obj", None) is None:
            def _run(inputs, params):
                return self.call(inputs, params)

            self._jit_raw_obj = jax.jit(_run)
        return self._jit_raw_obj

    def vmapped(self):
        """Compiled row-level entry: the cell program vmapped over the lead
        axis (``map_rows``'s engine).  Cached like ``jitted``; params are
        broadcast (not vmapped) and traced as arguments."""
        if self._vmapped is None:
            self._vmapped = self._bind_live_params(self._vmap_raw())
        return self._vmapped

    def _vmap_raw(self):
        """Raw row-level jit object (see :meth:`_jit_raw`)."""
        if getattr(self, "_vmap_raw_obj", None) is None:
            def _run(inputs, params):
                return jax.vmap(
                    lambda ins: self.call(ins, params), in_axes=(0,)
                )(inputs)

            self._vmap_raw_obj = jax.jit(_run)
        return self._vmap_raw_obj

    def _bind_live_params(self, compiled):
        """Bind the CURRENT params as the trailing traced argument at every
        call — the one place where the live-params calling convention lives.

        The params handed over are the ones resident on the device the
        call's inputs are committed to (:meth:`_params_at`), so a block
        on a pool device does not begin by copying every weight across."""
        return lambda *args: compiled(
            *args, self._params_at(_committed_device(args))
        )

    def _residency_now(self) -> _Residency:
        res = self._residency
        if res is None or not res.current(self._params):
            res = self._residency = _Residency(self._params)
        return res

    def _params_at(self, device) -> Mapping[str, Any]:
        """The live params for a call whose inputs are committed to
        ``device``: the originals when it is None (nothing committed, or
        a sharding over several devices) or their own device, else the
        replica resident there, placed once by the first such call."""
        params = self._params
        if device is None or not params:
            return params
        res = self._residency_now()
        if device == res.home or res.spread:
            return params
        return res.replica(device)

    def params_resident(self, device) -> bool:
        """Whether the live params are on ``device`` — the originals'
        own device or one that holds a replica of the current state — so
        that a block which ran there was called without a copy."""
        if not self._params:
            return True
        return device is not None and self._residency_now().resident(device)

    # cap on derived compiled callables kept per Program; least-recently
    # USED evicted first so a Program reused across many short-lived
    # meshes/executors does not pin their executables forever
    _DERIVED_CAP = 32

    def _derived_hit(self, key):
        """LRU touch: re-insert ``key`` so eviction order is recency of
        *use*, not insertion — a hot executable cannot be evicted by a
        burst of one-off keys."""
        self._derived[key] = self._derived.pop(key)
        return self._derived[key]

    def cached_jit(self, key, build_raw, **jit_kwargs):
        """Memoize ``jax.jit(build_raw(), **jit_kwargs)`` with live params
        bound.

        The verb engines build per-verb wrappers (pairwise folds, block
        reducers, shard_maps, donated prefetch entries) whose last
        positional argument is the params dict; caching them here keyed by
        verb/mode/mesh means repeated verb invocations on the same Program
        reuse one jit cache instead of re-tracing per call, and
        ``update_params`` takes effect without recompiling.  Eviction is
        LRU (a hit re-inserts the key).  ``build_raw`` returns the raw
        traceable ``fn(*args, params)``; ``jit_kwargs`` (e.g.
        ``donate_argnums``) must be part of ``key`` when they vary."""
        if key in self._derived:
            return self._derived_hit(key)
        while len(self._derived) >= self._DERIVED_CAP:
            self._derived.pop(next(iter(self._derived)))
        raw = jax.jit(build_raw(), **jit_kwargs)
        bound = self._bind_live_params(raw)
        # the raw jit object rides along so AOT warmup can lower the
        # EXACT entry the verbs execute (same module name, same donation
        # aliasing -> same persistent-cache key)
        bound.raw_jit = raw
        self._derived[key] = bound
        return bound

    # -- ahead-of-time compilation (persistent-cache cold start) -------------

    def _input_structs(
        self, input_specs: Mapping[str, Any]
    ) -> Dict[str, jax.ShapeDtypeStruct]:
        """Normalize ``input name -> (ScalarType, Shape) | ShapeDtypeStruct``
        into concrete ShapeDtypeStructs (static shapes required)."""
        structs: Dict[str, jax.ShapeDtypeStruct] = {}
        for n in self._input_names:
            if n not in input_specs:
                raise ProgramError(
                    f"no spec for program input {n!r}; got specs for "
                    f"{sorted(input_specs)}"
                )
            spec = input_specs[n]
            if isinstance(spec, jax.ShapeDtypeStruct):
                shape, dt = tuple(spec.shape), spec.dtype
            else:
                st, shape = spec
                shape, dt = tuple(Shape(shape)), st.np_dtype
            if any(d == UNKNOWN for d in shape):
                raise ProgramError(
                    f"input {n!r}: AOT compilation needs a static shape, "
                    f"got {shape} (bucket the lead dim first)"
                )
            structs[n] = jax.ShapeDtypeStruct(shape, dt)
        return structs

    def aot_compile(self, input_specs: Mapping[str, Any], rows_level=False):
        """Ahead-of-time ``lower().compile()`` at one exact (bucketed)
        input signature; returns the bound executable ``fn(inputs) ->
        {name: array}``.

        Memoized per (entry, input signature) in the derived-callable
        LRU; the returned callable carries ``.fingerprint``, a
        cross-process content hash of its lowered StableHLO (two Program
        objects wrapping the same source at the same bucket signature
        produce the same fingerprint, hence share one disk entry).  With
        the persistent compilation cache configured
        (``TFS_COMPILE_CACHE`` / :mod:`tensorframes_tpu.compile_cache`),
        the ``compile()`` step is a disk fetch in any process that has
        ever compiled this (fingerprint, signature) — a cold serving
        replica warms every bucket executable without running XLA.
        ``rows_level``: compile the vmapped cell-program entry
        (``map_rows``) instead of the block entry.

        The returned callable requires inputs matching the signature
        exactly (that is what bucketing guarantees); the engine's jitted
        entries remain the general path (they share the same raw jit
        object, so the persistent entry compiled here is the one they
        fetch)."""
        raw = self._vmap_raw() if rows_level else self._jit_raw()
        return self.aot_compile_raw(
            raw, input_specs, ("aot", bool(rows_level))
        )

    def aot_compile_raw(self, raw_jit, input_specs: Mapping[str, Any], tag):
        """:meth:`aot_compile` for an arbitrary raw jit entry of this
        program (``fn(inputs, params)``) — the engine passes its own
        donated entries (``cached_jit(...).raw_jit``) so warmup lowers
        exactly what the verbs will execute: same module name, same
        donation aliasing, hence the same persistent-cache key.  ``tag``
        namespaces the memo key in the derived-callable LRU.

        The fingerprint on the returned callable hashes the lowered
        StableHLO — no extra trace (``lower()`` already produced it) —
        and is stable across processes for the same program source and
        signature."""
        structs = self._input_structs(input_specs)
        sig = tuple(
            (n, structs[n].shape, str(structs[n].dtype))
            for n in sorted(structs)
        )
        key = (tag, sig)
        if key in self._derived:
            return self._derived_hit(key)
        param_specs = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
            self._params,
        )
        with observability.suppress_trace_count():
            lowered = raw_jit.lower(structs, param_specs)
            h = hashlib.sha256()
            h.update(jax.__version__.encode())
            h.update(lowered.as_text().encode())
            compiled = lowered.compile()
        fn = lambda inputs: compiled(inputs, self._params)  # noqa: E731
        fn.fingerprint = h.hexdigest()[:16]
        fn.signature = sig
        while len(self._derived) >= self._DERIVED_CAP:
            self._derived.pop(next(iter(self._derived)))
        self._derived[key] = fn
        return fn

    # -- serialization -------------------------------------------------------

    def serialize(self, input_specs: Mapping[str, Any]) -> bytes:
        """Freeze into a portable program artifact (StableHLO via
        ``jax.export``).

        The reference's program transport is frozen GraphDef bytes shipped
        to executors (``SerializedGraph``, ``TensorFlowOps.scala:21-61``);
        the XLA-native equivalent is serialized StableHLO: params are baked
        in as constants (a *frozen* program), and Unknown (-1) dims become
        symbolic — every Unknown lead dim shares one ``rows`` symbol (all
        columns of a block have the same row count), so one artifact serves
        any block size without recompiling the export.

        ``input_specs``: input name -> (ScalarType, Shape), Unknown dims
        allowed.  Round-trip via :func:`deserialize_program`.
        """
        import json

        from jax import export as jexp

        shapes: Dict[str, Shape] = {}
        stypes: Dict[str, Any] = {}
        for n in self._input_names:
            if n not in input_specs:
                raise ProgramError(
                    f"serialize: no spec for program input {n!r}; got "
                    f"specs for {sorted(input_specs)}"
                )
            spec = input_specs[n]
            if isinstance(spec, jax.ShapeDtypeStruct):
                shapes[n] = Shape(spec.shape)
                stypes[n] = spec.dtype
            else:
                st, shape = spec
                shapes[n] = Shape(shape)
                stypes[n] = st.np_dtype

        n_cell_syms = sum(
            sum(1 for d in s.dims[1:] if d == UNKNOWN)
            for s in shapes.values()
        )
        sym_names = ["rows"] + [f"u{i}" for i in range(n_cell_syms)]
        syms = list(jexp.symbolic_shape(", ".join(sym_names)))
        rows_sym, cell_syms = syms[0], syms[1:]
        next_cell = iter(cell_syms)
        structs = {}
        for n in self._input_names:
            dims = []
            for i, d in enumerate(shapes[n]):
                if d != UNKNOWN:
                    dims.append(d)
                elif i == 0:
                    dims.append(rows_sym)
                else:
                    dims.append(next(next_cell))
            structs[n] = jax.ShapeDtypeStruct(tuple(dims), stypes[n])

        with observability.suppress_trace_count():
            exported = jexp.export(jax.jit(lambda ins: self.call(ins)))(
                structs
            )
        header = json.dumps(
            {
                "format": "tfs-program-v1",
                "inputs": self._input_names,
                "fetches": self._fetches or self.fetches,
                "feed": self._feed,
            }
        ).encode()
        return header + b"\x00" + exported.serialize()

    # -- analysis ------------------------------------------------------------

    def analyze(
        self,
        input_specs: Mapping[str, Any],
        hints: Optional[Mapping[str, Sequence[int]]] = None,
    ) -> List[GraphNodeSummary]:
        """Shape-infer the program against input specs without executing it.

        ``input_specs``: input name -> (ScalarType, Shape) or ShapeDtypeStruct.
        Specs may contain Unknown (-1) dims: the program is shape-evaluated at
        two probe substitutions and output dims that depend on the unknown
        inputs come back Unknown (the lattice merge ``analyze`` uses for data,
        applied to programs).

        ``hints``: output name -> shape override (the ``ShapeDescription``
        mechanism, ``ShapeDescription.scala:3-16``), merged over any hints
        already attached via ``with_shape_hints``.  Hints *refine* inferred
        shapes — an Unknown dim becomes the hinted value, a concrete dim must
        agree (contradictions raise), mirroring the reference's hint-override
        with the stronger never-contradict guarantee.
        """
        shapes: Dict[str, Shape] = {}
        stypes: Dict[str, Any] = {}
        for n in self._input_names:
            if n not in input_specs:
                raise ProgramError(
                    f"analyze: no spec for program input {n!r}; "
                    f"got specs for {sorted(input_specs)}"
                )
            spec = input_specs[n]
            if isinstance(spec, jax.ShapeDtypeStruct):
                shapes[n] = Shape(spec.shape)
                stypes[n] = spec.dtype
            else:
                st, shape = spec
                shapes[n] = Shape(shape)
                stypes[n] = st.np_dtype

        def _eval(probe: int):
            structs = {
                n: jax.ShapeDtypeStruct(
                    tuple(probe if d == UNKNOWN else d for d in shapes[n]),
                    stypes[n],
                )
                for n in self._input_names
            }
            with observability.suppress_trace_count():
                return jax.eval_shape(lambda ins: self.call(ins), structs)

        has_unknown = any(not s.is_static for s in shapes.values())
        out_a = _eval(3)
        out_shapes: Dict[str, Shape] = {}
        if has_unknown:
            # dims that track the probe are Unknown; dims stable across
            # probes are genuinely static (the analyze lattice merge)
            out_b = _eval(7)
            for name in out_a:
                sa, sb = Shape(out_a[name].shape), Shape(out_b[name].shape)
                if sa.rank != sb.rank:
                    raise ProgramError(
                        f"analyze: output {name!r} changes rank with the "
                        f"unknown input dims ({sa} vs {sb}); its shape "
                        f"cannot be described"
                    )
                out_shapes[name] = sa.merge(sb)
        else:
            out_shapes = {n: Shape(s.shape) for n, s in out_a.items()}

        merged_hints = dict(self._shape_hints)
        for name, h in (hints or {}).items():
            merged_hints[name] = Shape(h)
        unknown_hints = sorted(set(merged_hints) - set(out_shapes))
        if unknown_hints:
            raise ProgramError(
                f"shape hints given for non-existent outputs: "
                f"{unknown_hints}; program outputs are {sorted(out_shapes)}"
            )

        summaries: List[GraphNodeSummary] = []
        for n in self._input_names:
            summaries.append(
                GraphNodeSummary(
                    n, True, False, dtypes.from_numpy(stypes[n]), shapes[n]
                )
            )
        for name, shape in out_shapes.items():
            if name in merged_hints:
                try:
                    shape = shape.refine(
                        merged_hints[name], context=f"output {name!r}"
                    )
                except Exception as e:
                    raise ProgramError(str(e)) from e
            summaries.append(
                GraphNodeSummary(
                    name,
                    False,
                    True,
                    dtypes.from_numpy(out_a[name].dtype),
                    shape,
                )
            )
        return summaries
