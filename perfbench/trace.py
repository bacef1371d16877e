"""Reduction of a profiler trace (xplane) to the numbers the metrics read.

A trace is taken as plain data, `[{"name": plane, "lines": [{"name": line,
"events": [(name, start_ns, duration_ns), ...]}]}]`, so that the reduction can
be checked on a small synthetic trace (`tests/test_trace.py`).  `load` turns
the profiler's file into that form with nothing but jax.

Device planes are those named `/device:TPU:<n>`; their `XLA Ops` line holds
one event per operation and `XLA Modules` one per executable run.  The
harness's own spans (`jax.profiler.TraceAnnotation("bench:...")`) lie on the
host plane; `bench:window` marks the traced window on the trace's clock.
"""

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN, SPAN_PREFIX = "bench:window", "bench:"
SMALL_GAP_NS = 10_000


def load(path):
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                               for e in line.events]}
                   for line in plane.lines]}
        for plane in data.planes
    ]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """(name, self time) per event of one line: its duration less the time of
    the events nested inside it (a `while` holds its body's operations)."""
    out, stack = [], []  # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    out.extend((s[0], s[2]) for s in stack)
    return out


def module_name(event_name):
    """`jit_paged_decode_step(1234567)` -> `jit_paged_decode_step`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(planes):
    """Numbers of one traced window; None if it holds no window span or no
    device operation.  Times in seconds; per-device lists are in device order."""
    spans = [(n, s, s + d) for p in planes if not DEVICE_PLANE.match(p["name"])
             for l in p["lines"] for n, s, d in l["events"] if n.startswith(SPAN_PREFIX)]
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    devices = sorted((int(DEVICE_PLANE.match(p["name"]).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p["name"]))
    if not window or not devices:
        return None
    w0, w1 = window[0]
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    busy, modules, ops, gaps = [], collections.Counter(), collections.Counter(), collections.Counter()
    module_runs = collections.Counter()
    for _, plane in devices:
        clipped = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                   for n, s, d in _line(plane, OPS_LINE) if s < w1 and s + d > w0]
        merged = _union([(s, s + d) for _, s, d in clipped])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, t in _self_times(clipped):
            ops[name] += t / 1e9
        for name, s, d in _line(plane, MODULES_LINE):
            if s >= w0 and s + d <= w1:  # whole runs only, so that time per run is exact
                modules[module_name(name)] += d / 1e9
                module_runs[module_name(name)] += 1
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            mid = (a + b) / 2
            if b - a < SMALL_GAP_NS:
                gaps["(gaps under 10 us, between operations)"] += (b - a) / 1e9
                continue
            over = [sp for sp in spans if sp[1] <= mid < sp[2]]
            name = min(over, key=lambda sp: sp[2] - sp[1])[0] if over else "(no benchmark span)"
            gaps[name] += (b - a) / 1e9
    if not any(busy):
        return None
    n = len(devices)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n,
        "busy_s_per_device": busy,
        "module_s": dict(modules),
        "module_runs": dict(module_runs),
        "device_ops": [[k[:96], v / n] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v / n] for k, v in gaps.most_common(10)],
    }
