"""Roofline analysis of compiled XLA executables.

The question every flat benchmark line raises — "is this the chip's
ceiling or our tuning debt?" — has a standard quantitative answer: the
roofline model.  For each operation, the attainable throughput is

    attainable_flops = min(peak_flops, intensity * peak_bytes_per_s)

where ``intensity = flops / bytes_accessed`` is the op's arithmetic
intensity.  An executable's *shape-mix ceiling* follows by time-weighting:
the wall time of op ``i`` is bounded below by
``max(flops_i / peak_flops, bytes_i / peak_bytes_per_s)``, so

    ceiling_tflops = total_flops / sum_i time_lb_i
    ceiling_mfu    = ceiling_tflops / peak_tflops

``ceiling_mfu`` is the MFU an ideal scheduler could reach on this exact
op mix — measured MFU at >= ~0.9x of it means the workload is at the
hardware's envelope (flat is then fine forever); a large gap means
tuning headroom (VERDICT r5 weak #1 / next #3).

Two granularities, best-effort in this order:

* **per-op**: the optimized HLO text (``Compiled.as_text()``) is walked;
  ``dot`` and ``convolution`` FLOPs are computed from their printed
  shapes/attributes (contracting dims, kernel spatial dims,
  ``feature_group_count``), fusions inherit the dot/conv FLOPs of their
  called computations, and every op's bytes come from its operand +
  result buffer sizes.  Unparseable instructions degrade to bytes-only
  (they still contribute bandwidth time) — the pass never raises on
  unknown HLO.
* **aggregate**: when the text yields no per-op FLOPs at all (exotic
  backends, custom-call-only modules), ``Compiled.cost_analysis()``'s
  module totals produce a single-op roofline (``source="aggregate"``).

Peaks come from the public spec-sheet tables below (bf16 FLOP/s and HBM
bandwidth per chip) keyed by ``device_kind``, or pass ``peak_flops`` /
``peak_bytes_per_s`` explicitly for devices not listed (CPU test runs
do).  This module never executes the program: analysis is compile-only.

``bench.py`` emits the report next to the measured MFU so the parsed
telemetry carries ``ceiling_mfu`` alongside ``mfu``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

# bf16 peak FLOP/s per chip by device kind (public spec sheets) — the
# single source for bench.py's MFU math too.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# HBM bandwidth, bytes/s per chip (public spec sheets)
PEAK_BYTES_PER_S = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}

# HLO primitive type -> bytes per element
_TYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(?:\([^)]*\)|[a-z]\d*[a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([\w\-]+)\("
)


def _shape_elems(dims: str) -> int:
    if not dims:
        return 1
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n


def _shape_bytes(ty: str, dims: str) -> float:
    return _shape_elems(dims) * _TYPE_BYTES.get(ty, 4)


@dataclasses.dataclass
class OpRoofline:
    """One entry-computation instruction's roofline position."""

    name: str
    kind: str  # HLO opcode: dot | convolution | fusion | ...
    flops: float
    bytes: float
    attainable_tflops: float  # min(peak, intensity * bw) / 1e12
    time_lb_s: float  # max(flops/peak, bytes/bw)

    @property
    def intensity(self) -> float:
        return self.flops / self.bytes if self.bytes else 0.0


@dataclasses.dataclass
class RooflineReport:
    """Shape-mix roofline of one compiled executable."""

    device_kind: str
    peak_tflops: float
    peak_gbytes_per_s: float
    total_flops: float
    total_bytes: float
    ceiling_tflops: float
    ceiling_mfu: float
    ops: List[OpRoofline]
    source: str  # "hlo" (per-op parse) | "aggregate" (cost_analysis)
    xla_flops: Optional[float] = None  # module total per cost_analysis
    # filled when measured_s is passed to roofline():
    measured_s: Optional[float] = None
    achieved_tflops: Optional[float] = None
    mfu: Optional[float] = None
    ceiling_fraction: Optional[float] = None  # mfu / ceiling_mfu

    def summary(self, top: int = 5) -> Dict[str, Any]:
        """JSON-able digest: the ceiling plus the ``top`` ops by
        time-lower-bound (the ops that define the ceiling)."""
        worst = sorted(self.ops, key=lambda o: -o.time_lb_s)[:top]
        out: Dict[str, Any] = {
            "device": self.device_kind,
            "peak_tflops": round(self.peak_tflops, 1),
            "peak_gbytes_per_s": round(self.peak_gbytes_per_s, 1),
            "ceiling_tflops": round(self.ceiling_tflops, 2),
            "ceiling_mfu": round(self.ceiling_mfu, 4),
            "source": self.source,
            "total_gflops": round(self.total_flops / 1e9, 3),
            "top_ops": [
                {
                    "op": f"{o.kind}:{o.name}",
                    "gflops": round(o.flops / 1e9, 3),
                    "mbytes": round(o.bytes / 1e6, 3),
                    "intensity": round(o.intensity, 1),
                    "attainable_tflops": round(o.attainable_tflops, 2),
                    "time_share": round(
                        o.time_lb_s
                        / max(sum(p.time_lb_s for p in self.ops), 1e-30),
                        3,
                    ),
                }
                for o in worst
            ],
        }
        if self.mfu is not None:
            out["mfu"] = round(self.mfu, 4)
            out["achieved_tflops"] = round(self.achieved_tflops, 2)
            # stays None when ceiling_mfu is 0 (no FLOPs found anywhere)
            if self.ceiling_fraction is not None:
                out["ceiling_fraction"] = round(self.ceiling_fraction, 3)
        return out


# ---------------------------------------------------------------------------
# HLO text walk
# ---------------------------------------------------------------------------


def _split_computations(hlo: str) -> Tuple[List[str], Dict[str, List[str]]]:
    """(entry instruction lines, computation name -> instruction lines)."""
    comps: Dict[str, List[str]] = {}
    entry: List[str] = []
    cur: Optional[List[str]] = None
    is_entry = False
    for line in hlo.splitlines():
        s = line.strip()
        if s.endswith("{") and ("(" in s or s.startswith("ENTRY")):
            name_m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", s)
            cur = []
            is_entry = s.startswith("ENTRY")
            if name_m:
                comps[name_m.group(1)] = cur
            continue
        if s == "}" or s.startswith("}"):
            if is_entry and cur is not None:
                entry = cur
            cur = None
            is_entry = False
            continue
        if cur is not None and "=" in s:
            cur.append(s)
    return entry, comps


def _dot_flops(line: str) -> float:
    """2 * out_elems * prod(contracting dims of the lhs)."""
    shapes = _SHAPE_RE.findall(line)
    if len(shapes) < 3:
        return 0.0
    out_dims = [int(d) for d in shapes[0][1].split(",") if d]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    lhs_dims = [int(d) for d in shapes[1][1].split(",") if d]
    k = 1
    if m:
        for di in m.group(1).split(","):
            if di:
                k *= lhs_dims[int(di)]
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    return 2.0 * out_elems * k


def _conv_flops(line: str) -> float:
    """2 * out_elems * prod(kernel spatial) * kernel_input_features.

    The kernel's input-feature dim is already Cin/feature_group_count in
    XLA's convention, so grouped convs need no extra division.  Counts
    the dense MAC upper bound (padding positions included) — a few
    percent above XLA's own count on padded convs, which only makes the
    ceiling conservative."""
    shapes = _SHAPE_RE.findall(line)
    if len(shapes) < 3:
        return 0.0
    m = re.search(r"dim_labels=\w+_(\w+)->", line)
    if not m:
        return 0.0
    rhs_labels = m.group(1)
    rhs_dims = [int(d) for d in shapes[2][1].split(",") if d]
    if len(rhs_labels) != len(rhs_dims):
        return 0.0
    k = 1
    for lab, d in zip(rhs_labels, rhs_dims):
        if lab != "o":  # spatial digits and the input-feature 'i' dim
            k *= d
    out_elems = _shape_elems(shapes[0][1])
    return 2.0 * out_elems * k


def _line_flops(line: str, opcode: str, comps: Dict[str, List[str]]) -> float:
    if opcode == "dot":
        return _dot_flops(line)
    if opcode == "convolution":
        return _conv_flops(line)
    if opcode == "fusion":
        m = re.search(r"calls=%?([\w.\-]+)", line)
        if not m or m.group(1) not in comps:
            return 0.0
        total = 0.0
        for inner in comps[m.group(1)]:
            im = _INSTR_RE.match(inner)
            if not im:
                continue
            iop = im.group(2)
            if iop in ("dot", "convolution"):
                total += _line_flops(inner, iop, comps)
        return total
    return 0.0


def _parse_ops(hlo: str) -> List[Tuple[str, str, float, float]]:
    """Per entry instruction: (name, opcode, flops, bytes)."""
    entry, comps = _split_computations(hlo)
    ops: List[Tuple[str, str, float, float]] = []
    for line in entry:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        if opcode in ("parameter", "constant", "get-tuple-element", "tuple"):
            continue
        try:
            nbytes = sum(
                _shape_bytes(ty, dims) for ty, dims in _SHAPE_RE.findall(line)
            )
            flops = _line_flops(line, opcode, comps)
        except Exception:
            continue
        ops.append((name, opcode, flops, nbytes))
    return ops


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _resolve_compiled(target, args, kwargs):
    """Accept a Compiled, a Lowered, or a jittable fn + example args."""
    if hasattr(target, "cost_analysis") and hasattr(target, "as_text"):
        if hasattr(target, "compile"):  # a Lowered
            return target.compile()
        return target  # already Compiled
    import jax

    fn = target
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    return fn.lower(*args, **(kwargs or {})).compile()


def _aggregate_cost(compiled) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes accessed) from ``cost_analysis`` (None where the
    backend reports no analysis)."""
    ca = compiled.cost_analysis() or {}
    return ca.get("flops"), ca.get("bytes accessed")


def roofline(
    target,
    *args,
    measured_s: Optional[float] = None,
    device_kind: Optional[str] = None,
    peak_flops: Optional[float] = None,
    peak_bytes_per_s: Optional[float] = None,
    **kwargs,
) -> RooflineReport:
    """Roofline-analyze a compiled executable (or compile-and-analyze a
    jittable ``target`` against example ``args``).

    ``measured_s``: the measured wall time of ONE execution — fills the
    achieved side (``mfu``, ``achieved_tflops``, ``ceiling_fraction``).
    ``device_kind`` defaults to the first local device's kind; peaks
    resolve from the spec tables, or pass them explicitly (required for
    device kinds not in the tables, e.g. CPU test runs)."""
    compiled = _resolve_compiled(target, args, kwargs)
    if device_kind is None:
        import jax

        device_kind = getattr(
            jax.devices()[0], "device_kind", "unknown"
        )
    if peak_flops is None:
        peak_flops = PEAK_FLOPS.get(device_kind)
    if peak_bytes_per_s is None:
        peak_bytes_per_s = PEAK_BYTES_PER_S.get(device_kind)
    if not peak_flops or not peak_bytes_per_s:
        raise ValueError(
            f"no peak specs for device kind {device_kind!r}; pass "
            f"peak_flops= and peak_bytes_per_s= explicitly (known kinds: "
            f"{sorted(PEAK_FLOPS)})"
        )

    xla_flops, xla_bytes = _aggregate_cost(compiled)
    try:
        parsed = _parse_ops(compiled.as_text())
    except Exception:
        parsed = []

    ops: List[OpRoofline] = []
    if any(f > 0 for _, _, f, _ in parsed):
        source = "hlo"
        for name, opcode, flops, nbytes in parsed:
            tl = max(flops / peak_flops, nbytes / peak_bytes_per_s)
            intensity = flops / nbytes if nbytes else 0.0
            ops.append(
                OpRoofline(
                    name,
                    opcode,
                    flops,
                    nbytes,
                    min(peak_flops, intensity * peak_bytes_per_s) / 1e12,
                    tl,
                )
            )
    else:
        source = "aggregate"
        flops = float(xla_flops or 0.0)
        nbytes = float(xla_bytes or 0.0)
        tl = max(flops / peak_flops, nbytes / peak_bytes_per_s)
        intensity = flops / nbytes if nbytes else 0.0
        ops = [
            OpRoofline(
                "module",
                "aggregate",
                flops,
                nbytes,
                min(peak_flops, intensity * peak_bytes_per_s) / 1e12,
                tl,
            )
        ]

    total_flops = sum(o.flops for o in ops)
    total_bytes = sum(o.bytes for o in ops)
    time_lb = sum(o.time_lb_s for o in ops)
    ceiling_tflops = total_flops / time_lb / 1e12 if time_lb > 0 else 0.0
    report = RooflineReport(
        device_kind=device_kind,
        peak_tflops=peak_flops / 1e12,
        peak_gbytes_per_s=peak_bytes_per_s / 1e9,
        total_flops=total_flops,
        total_bytes=total_bytes,
        ceiling_tflops=ceiling_tflops,
        ceiling_mfu=ceiling_tflops * 1e12 / peak_flops,
        ops=ops,
        source=source,
        xla_flops=xla_flops,
    )
    if measured_s is not None and measured_s > 0:
        # achieved MFU counts XLA's own flops when available (matches the
        # bench's long-standing MFU methodology), else the parsed total
        ach_flops = float(xla_flops) if xla_flops else total_flops
        report.measured_s = measured_s
        report.achieved_tflops = ach_flops / measured_s / 1e12
        report.mfu = ach_flops / measured_s / peak_flops
        if report.ceiling_mfu > 0:
            report.ceiling_fraction = report.mfu / report.ceiling_mfu
    return report
