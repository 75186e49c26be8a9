"""The program's own spans in a profiler trace, and a traced run that
reads them.

The program opens ``jax.profiler.TraceAnnotation`` spans named
``repro.<layer>.<step>`` at the boundaries of its host-side layers: the
design space (``core/space.py``: evaluate, lower, assemble), the engines
(``core/flitsim.py``: probe, core, escalate, readback) and the stream
(``core/streaming.py``: marshal, dispatch, retire, winners), plus
``repro.compile`` around a compile.  They sit on the host plane, on the
clock the device planes share, so the device's idle time inside each of
them can be read.

:func:`reduce_spans` counts the spans that lie whole inside the
``bench.query`` spans of the trace, or, where the trace holds none (a
stretch inside one long streamed query), whole inside the stretch (first
to last device event).  Per span name (the ``#key=value#`` metadata
suffix stripped) it gives ``n``, ``total_s``, ``self_s`` (total less the
child ``repro.*`` spans on the same thread), ``idle_s`` (device-idle
time inside the span, averaged over the chips read) and ``idle_self_s``
(the same, less the children's), and for the whole region its length,
its device-idle time and the part of that no ``repro.*`` span covers (a
span the stretch cuts covers its part inside).

Run as a script, it makes one traced run of a cell exactly as
``run.py --trace 1`` does and prints its result line with the span
reduction of the same trace (:func:`traced_run`) and what a span costs
with the profiler off::

    python3 benchmarks/chip/span_reduce.py --workload joint_space.1e7 \\
        --seed 1234 --seconds 30
"""
import time

T_PROCESS = time.perf_counter()

import bisect  # noqa: E402
import glob  # noqa: E402
import itertools  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from trace_reduce import (  # noqa: E402
    DEVICE_PLANE, MODULE_LINES, OP_LINES, QUERY_SPAN, clip, union,
)

PREFIX = "repro."

Span = Tuple[float, float, str]


def span_name(event_name: str) -> str:
    """``repro.engine.probe#family=flitsim.symmetric#`` ->
    ``repro.engine.probe``."""
    return event_name.split("#", 1)[0]


class Busy:
    """Merged busy intervals of one device, with the busy time before
    each interval, so the busy time inside any span is two bisections."""

    def __init__(self, merged: Sequence[Tuple[float, float]]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.before = list(itertools.accumulate(
            (b - a for a, b in merged), initial=0.0))

    def until(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.before[i] + min(t, self.ends[i]) - self.starts[i]

    def within(self, a: float, b: float) -> float:
        return self.until(b) - self.until(a)


def idle_in(busy: Sequence[Busy], a: float, b: float) -> Optional[float]:
    """Device-idle time inside ``[a, b]``, averaged over the devices."""
    if not busy:
        return None
    return sum((b - a) - d.within(a, b) for d in busy) / len(busy)


def nest(spans: Sequence[Span]) -> List[Optional[int]]:
    """Index of each span's innermost enclosing span (same thread), or
    ``None``; ``spans`` sorted by start, the longer first on a tie."""
    parent: List[Optional[int]] = []
    stack: List[int] = []
    for i, (a, b, _) in enumerate(spans):
        while stack and not (spans[stack[-1]][0] <= a
                             and b <= spans[stack[-1]][1]):
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    return parent


def span_totals(threads: Sequence[Sequence[Span]], busy: Sequence[Busy],
                regions: Sequence[Tuple[float, float]]) -> Dict[str, Any]:
    """The reduction of :func:`reduce_spans` from the ``repro.*`` spans
    of each host thread, each device's busy intervals and the regions a
    span must lie in whole to count."""
    starts = [a for a, _ in regions]

    def counted(a: float, b: float) -> bool:
        i = bisect.bisect_right(starts, a) - 1
        return i >= 0 and b <= regions[i][1]

    out: Dict[str, Dict[str, Any]] = {}
    for thread in threads:
        spans = sorted(thread, key=lambda s: (s[0], -s[1]))
        parent = nest(spans)
        length = [b - a for a, b, _ in spans]
        idle = [idle_in(busy, a, b) for a, b, _ in spans]
        child_len = [0.0] * len(spans)
        child_idle = [0.0] * len(spans)
        for i, p in enumerate(parent):
            if p is not None:
                child_len[p] += length[i]
                child_idle[p] += idle[i] or 0.0
        for i, (a, b, name) in enumerate(spans):
            if not counted(a, b):
                continue
            row = out.setdefault(name, {"n": 0, "total_s": 0.0,
                                        "self_s": 0.0, "idle_s": None,
                                        "idle_self_s": None})
            row["n"] += 1
            row["total_s"] += length[i] * 1e-9
            row["self_s"] += (length[i] - child_len[i]) * 1e-9
            if idle[i] is not None:
                row["idle_s"] = (row["idle_s"] or 0.0) + idle[i] * 1e-9
                row["idle_self_s"] = ((row["idle_self_s"] or 0.0)
                                      + (idle[i] - child_idle[i]) * 1e-9)
    region_idle = [idle_in(busy, a, b) for a, b in regions]
    every = [(a, b) for thread in threads for a, b, _ in thread]
    covered = [idle_in(busy, a, b) for a, b in union(
        c for lo, hi in regions for c in clip(every, lo, hi))]
    has_idle = bool(busy)
    return {
        "spans": out,
        "regions": len(regions),
        "region_s": sum(b - a for a, b in regions) * 1e-9,
        "region_idle_s": (sum(region_idle) * 1e-9 if has_idle else None),
        "uncovered_idle_s": ((sum(region_idle) - sum(covered)) * 1e-9
                             if has_idle else None),
    }


def reduce_spans(pd, device_ids: Optional[Sequence[int]] = None
                 ) -> Dict[str, Any]:
    """The span reduction of one profile (see the module docstring)."""
    busy_by_device: Dict[int, List[Tuple[float, float]]] = {}
    threads: List[List[Span]] = []
    queries: List[Tuple[float, float]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if device_ids is not None and dev not in device_ids:
                continue
            busy_by_device[dev] = union(
                (e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines
                if line.name in MODULE_LINES + OP_LINES
                for e in line.events)
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  span_name(e.name)))
                elif e.name == QUERY_SPAN:
                    queries.append((e.start_ns,
                                    e.start_ns + e.duration_ns))
            if spans:
                threads.append(spans)
    busy = [Busy(v) for _, v in sorted(busy_by_device.items())]
    if queries:
        regions = union(queries)
    else:
        ends = [x for v in busy_by_device.values() if v
                for x in (v[0][0], v[-1][1])]
        regions = [(min(ends), max(ends))] if ends else []
    return span_totals(threads, busy, regions)


def load_profile(log_dir: str):
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return jax.profiler.ProfileData.from_file(paths[-1])


def span_cost_us(n: int = 200_000) -> Dict[str, float]:
    """Host time of one empty span with the profiler off, in
    microseconds: without metadata, and with one keyword."""
    import jax
    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    for _ in range(n):
        pass
    t1 = time.perf_counter()
    for _ in range(n):
        with ann("repro.space.lower"):
            pass
    t2 = time.perf_counter()
    for i in range(n):
        with ann("repro.stream.dispatch", index=i):
            pass
    t3 = time.perf_counter()
    loop = t1 - t0
    return {"plain": 1e6 * (t2 - t1 - loop) / n,
            "one_keyword": 1e6 * (t3 - t2 - loop) / n}


def traced_run(cell, seed: int, seconds: float, *, t_process: float,
               **run_kw) -> Dict[str, Any]:
    """One traced run of ``cell`` as ``run.py --trace 1`` makes it (the
    harness's :func:`run_cell`), with what the same trace says of the
    program's spans under ``span_reduction``: :func:`reduce_spans`, the
    runs of each program, the lengths of the whole queries and the count
    of each span name in the trace, whatever its place."""
    import harness
    import trace_reduce
    found: Dict[str, Any] = {}

    def reduce_dir(log_dir, device_ids=None):
        pd = load_profile(log_dir)
        found.update(reduce_spans(pd, device_ids))
        red = trace_reduce.reduce_profile(pd, device_ids)
        found["module_runs"] = {k: len(v)
                                for k, v in red["module_runs"].items()}
        found["query_s"] = [s for s, _ in red["queries"]]
        names: Dict[str, int] = {}
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((PREFIX, trace_reduce.SPAN_PREFIX)):
                        n = span_name(e.name)
                        names[n] = names.get(n, 0) + 1
        found["names"] = names
        return red

    own = trace_reduce.reduce_dir
    trace_reduce.reduce_dir = reduce_dir
    try:
        line = harness.run_cell(cell, seed, seconds, True,
                                t_process=t_process, **run_kw)
    finally:
        trace_reduce.reduce_dir = own
    line["span_reduction"] = found
    return line


def main() -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                    "src"))
    import harness
    cell = harness.find_cell(harness.load_spec(), args.workload)
    try:
        line = traced_run(cell, args.seed, args.seconds,
                          t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"span_reduce.py: {e}", file=sys.stderr)
        return 2
    line["span_cost_us"] = span_cost_us()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
