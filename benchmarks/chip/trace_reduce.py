"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers, read with nothing but ``jax.profiler.ProfileData``.

On a TPU each chip is a plane ``/device:TPU:<id>`` whose ``XLA Modules``
line holds one event per program execution and whose ``XLA Ops`` line
holds one event per executed HLO op (every loop iteration included).
The harness's own host spans (``jax.profiler.TraceAnnotation`` names
starting with ``bench.``) sit on the host plane ``/host:CPU``.  All
event times share one timeline, in ns from the start of the trace.

Per device the reduction gives the union of busy intervals (module and
op events together, so nested events count once), the executions and
time of each XLA module, and the ops that took the most time under
``module/op`` names; over all devices, the traced stretch (first to last
device event), the longest idle gaps of the first device, each labelled
with what the harness's thread was doing, and for each ``bench.query``
span the trace holds whole, its length and the device time inside it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
QUERY_SPAN = "bench.query"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between merged busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.6 = f32[128] fusion(...)`` -> ``fusion.6``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def innermost(spans: Sequence[Tuple[float, float, str]], t: float
              ) -> Optional[str]:
    """Name of the shortest span containing ``t``."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else None


def harness_lines(host_lines) -> List[Tuple[float, float, str]]:
    """The events of the harness's thread: the host lines holding one of
    its spans; where a stretch holds none (every span was opened before
    the trace started), the busiest line named after the process, which
    is the main thread's."""
    lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in line.events] for line in host_lines]
    own = [ev for ev in lines
           if any(name.startswith(SPAN_PREFIX) for _, _, name in ev)]
    if own:
        return [e for ev in own for e in ev]
    try:
        with open("/proc/self/comm") as f:
            comm = f.read().strip()
    except OSError:
        return []
    named = [ev for line, ev in zip(host_lines, lines) if line.name == comm]
    return max(named, key=len, default=[])


def reduce_profile(pd, device_ids: Optional[Sequence[int]] = None
                   ) -> Dict[str, Any]:
    """The device numbers of one profile (see the module docstring).

    The traced stretch runs from the first to the last device event of
    the chips read.  A span still open when the trace started or stopped
    is not in the trace: every ``bench.query`` span read is whole."""
    devices: Dict[int, Dict[str, Any]] = {}
    host_lines = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if device_ids is not None and dev not in device_ids:
                continue
            modules: List[Tuple[float, float, str]] = []
            ops: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                sink = (modules if line.name in MODULE_LINES
                        else ops if line.name in OP_LINES else None)
                if sink is None:
                    continue
                for e in line.events:
                    sink.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
            devices[dev] = {"modules": modules, "ops": ops}
        elif plane.name == HOST_PLANE:
            host_lines = list(plane.lines)
    host = harness_lines(host_lines)
    spans = [(a, b) for d in devices.values() for lst in d.values()
             for a, b, _ in lst]
    lo = min((a for a, _ in spans), default=0.0)
    hi = max((b for _, b in spans), default=lo)
    busy_by_device: Dict[int, List[Interval]] = {}
    op_time: Dict[str, float] = {}
    module_runs: Dict[str, List[Interval]] = {}
    for dev in sorted(devices):
        mods, ops = devices[dev]["modules"], devices[dev]["ops"]
        busy_by_device[dev] = union([(a, b) for a, b, _ in mods + ops])
        mod_sorted = sorted(mods)
        for a, b, name in mod_sorted:
            module_runs.setdefault(name, []).append((a, b))
        # attribute each op to the module execution that contains it
        j = 0
        for a, b, name in sorted(ops):
            while j < len(mod_sorted) and mod_sorted[j][1] < a:
                j += 1
            mod = (mod_sorted[j][2] if j < len(mod_sorted)
                   and mod_sorted[j][0] <= a else "?")
            key = f"{mod}/{op_name(name)}"
            op_time[key] = op_time.get(key, 0.0) + (b - a)
    n = max(len(busy_by_device), 1)
    first_busy = busy_by_device[min(busy_by_device)] if busy_by_device \
        else []
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    labelled = [[innermost(host, (a + b) / 2) or "outside", (b - a) * 1e-9]
                for a, b in idle[:10]]
    queries = [[(b - a) * 1e-9,
                sum(total(clip(busy, a, b))
                    for busy in busy_by_device.values()) / n * 1e-9]
               for a, b, name in sorted(host) if name == QUERY_SPAN]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(map(total, busy_by_device.values())) / n * 1e-9,
        "busy_s_by_device": {str(d): total(v) * 1e-9
                             for d, v in busy_by_device.items()},
        "devices": len(busy_by_device),
        "module_s": {k: total(v) * 1e-9 for k, v in module_runs.items()},
        "module_runs": {k: [(b - a) * 1e-9 for a, b in sorted(v)]
                        for k, v in module_runs.items()},
        "top_ops": [[k, v * 1e-9] for k, v in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": labelled,
        "queries": queries,
    }


def reduce_dir(log_dir: str, device_ids: Optional[Sequence[int]] = None
               ) -> Dict[str, Any]:
    """Reduce the newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    return reduce_profile(pd, device_ids)


def device_ms_per_query(ctx) -> Optional[float]:
    """Device time per whole query: the time in which an operation ran on
    the device (averaged over the chips used) inside the ``bench.query``
    spans the trace holds whole, over their number, in ms."""
    queries = (ctx.get("trace") or {}).get("queries") or []
    busy = sum(b for _, b in queries)
    return 1e3 * busy / len(queries) if busy > 0 else None


def query_idle_share(ctx) -> Optional[float]:
    """Share of the whole queries' time in which no operation ran on the
    device, over the ``bench.query`` spans the trace holds whole, in
    percent."""
    queries = (ctx.get("trace") or {}).get("queries") or []
    span = sum(s for s, _ in queries)
    busy = sum(b for _, b in queries)
    return 100.0 * (1.0 - busy / span) if busy > 0 else None


def program_ms_per_query(ctx, program: str) -> Optional[float]:
    """Device time per query of a program that runs once per dispatch:
    the mean time of its executions in the trace (per chip; the first
    and the last, which the stretch may cut, left out where there are
    more than two) times the mean ``dispatches`` of the window's
    queries, in ms."""
    runs = [d for name, ds in ((ctx.get("trace") or {}).get("module_runs")
                               or {}).items()
            if name == program or name.startswith(program + "(")
            for d in ds]
    runs = runs[1:-1] if len(runs) > 2 else runs
    dispatches = [c["dispatches"] for c in ctx.get("counters", [])
                  if c.get("dispatches")]
    if not runs or not dispatches or sum(runs) <= 0:
        return None
    return 1e3 * sum(runs) / len(runs) * sum(dispatches) / len(dispatches)


def idle_share(ctx) -> Optional[float]:
    """Share of the traced stretch (first to last device event) in which
    no operation ran on the device, averaged over the chips used, in
    percent."""
    red = ctx.get("trace")
    if not red or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
