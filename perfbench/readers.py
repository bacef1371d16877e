"""Readers of per-layer metrics.  A metric is a data file, `metrics/<name>.json`:
`{"reader": "<function here, or module:function>", ...arguments}`.  A reader
gets the run's observed quantities, `obs` (the driver's counts, `counters.*`
deltas over the window, `setup.*` counters, `trace.*` from the trace
reduction, `hbm_peak_bytes`, `chips`, `peak.*`), and returns a number, or
None where there is nothing to read: the metric is then left out of the line.
"""

import math
import re


def _get(obs, key):
    """A quantity by name; a list of names is their product."""
    if isinstance(key, list):
        vals = [_get(obs, k) for k in key]
        return None if any(v is None for v in vals) else math.prod(vals)
    return obs.get(key)


def value(obs, key, scale=1.0):
    v = _get(obs, key)
    return None if v is None else scale * v


def ratio(obs, num, den, scale=1.0, complement=False):
    """scale * num / den, or scale * (1 - num / den)."""
    n, d = _get(obs, num), _get(obs, den)
    if n is None or not d:
        return None
    return scale * (1.0 - n / d if complement else n / d)


def _module(obs, pattern):
    """(seconds, runs) of the executables whose module name matches."""
    secs = obs.get("trace.module_s") or {}
    runs = obs.get("trace.module_runs") or {}
    names = [n for n in secs if re.search(pattern, n)]
    return sum(secs[n] for n in names), sum(runs[n] for n in names)


def module_ms(obs, module):
    """Device milliseconds per run of an executable, from the trace."""
    secs, runs = _module(obs, module)
    return 1000.0 * secs / runs if runs else None


def roofline(obs, module, least):
    """The least time a run of the executable could take, over its device time."""
    secs, runs = _module(obs, module)
    floor = _get(obs, least)
    if not runs or not secs or floor is None:
        return None
    return 100.0 * floor / (secs / runs)


def skew(obs, key):
    """(max - min) / mean of a per-device list, in percent; nothing on one device."""
    vals = obs.get(key)
    if not vals or len(vals) < 2 or not sum(vals):
        return None
    return 100.0 * (max(vals) - min(vals)) / (sum(vals) / len(vals))


def module_share(obs, module):
    """Share of the traced window, per device, spent in an executable."""
    secs, runs = _module(obs, module)
    window = obs.get("trace.window_s")
    if not runs or not window:
        return None
    return 100.0 * secs / (window * len(obs.get("trace.busy_s_per_device") or [1]))
