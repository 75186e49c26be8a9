"""The chip benchmark's harness: finds a cell's configuration, traffic,
query runner and per-layer metric readers by the names in
``BENCHMARK.json``, runs one cell once, and prints the result line.

Layout under this directory (each found by name, none edited to add a
cell):

* ``configs/<config>.json``   the design space a user asks about;
* ``traffic/<traffic>.json``  the region queried (values the seed draws);
* ``runners/<runner>.py``     the query runner a configuration names;
* ``metrics/<metric>.py``     one reader per per-layer metric.

The harness talks to the program only through the runners, which call
its public entry points (``DesignSpace(...).evaluate``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
#: the checkout's root: BENCHMARK.json, src/ and the compile cache
ROOT = os.path.dirname(os.path.dirname(HERE))
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: where a traced run writes its profile (in the checkout, cleared per run)
TRACE_DIR = os.path.join(HERE, ".trace")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_spec(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` by its path (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def find_cell(spec: Dict[str, Any], workload: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; choose from "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json_path(cfg["file"])
    traffic = load_json("traffic", w["traffic"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


def load_json_path(relpath: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, relpath)) as f:
        return json.load(f)


# -- device -------------------------------------------------------------------


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs


def device_label(devs) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA backend compilations while armed (none may happen in
    the measured window)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, at the fixed path in
    this checkout; every program is cached, however fast it compiled."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


# -- one run ------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """What the measured window saw: whole queries only."""

    seconds: float = 0.0
    latencies: List[float] = dataclasses.field(default_factory=list)
    cells: int = 0
    attempted: int = 0
    failed: int = 0
    counters: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    #: (query index, the runner's digest of its answer) for checked queries
    results: List[Any] = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)
    compiles: int = 0


def run_window(runner, seconds: float, compiles: CompileCounter) -> Window:
    """Queries back to back until ``seconds`` have passed; the window
    ends at the boundary of the query that crosses it.  Reading the
    program's counters and keeping what the check needs of an answer are
    the harness's own work and are left out of the window's time."""
    import jax
    win = Window()
    compiles.armed = True
    t0 = time.perf_counter()
    own = 0.0
    i = 0
    while True:
        win.attempted += 1
        q0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.query"):
                res = runner.query(i)
        except Exception as e:                     # noqa: BLE001
            # a failed query is counted and reported; the window goes on
            win.failed += 1
            win.errors.append(f"query {i}: {type(e).__name__}: {e}")
            res = None
        q1 = time.perf_counter()
        if res is not None:
            win.latencies.append(q1 - q0)
            win.cells += runner.cells(res)
            win.counters.append(runner.counters())
            kept = runner.digest(i, res)
            if kept is not None:
                win.results.append((i, kept))
            del res
        i += 1
        elapsed = q1 - t0 - own
        own += time.perf_counter() - q1
        if elapsed >= seconds:
            break
    win.seconds = time.perf_counter() - t0 - own
    compiles.armed = False
    win.compiles = compiles.count
    return win


def trace_queries(runner, delay: float, seconds: float, log_dir: str
                  ) -> None:
    """Queries back to back while a second thread records ``seconds`` of
    them under the profiler, starting ``delay`` seconds in (past a long
    query's start-up, into its dispatch loop).  The queries go on until
    the trace has stopped.  A TPU trace holds every loop iteration of
    every program, so the traced stretch is kept short."""
    import threading
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    stopped = threading.Event()
    errors: List[BaseException] = []

    def record():
        try:
            time.sleep(delay)
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            time.sleep(seconds)
            jax.profiler.stop_trace()
        except BaseException as e:                 # noqa: BLE001
            errors.append(e)
        finally:
            stopped.set()

    tracer = threading.Thread(target=record, name="bench-tracer")
    tracer.start()
    i = 0
    try:
        while not stopped.is_set():
            with jax.profiler.TraceAnnotation("bench.query"):
                runner.query(i)
            i += 1
    finally:
        tracer.join()
    if errors:
        raise errors[0]


def p95(values: List[float]) -> float:
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def end_to_end(cell: Cell, runner, win: Window,
               setup_s: float) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics from the host clock."""
    rate = win.cells / win.seconds
    values = {"setup_s": setup_s,
              runner.RATE_METRIC: rate}
    if getattr(runner, "P95_METRIC", None) and win.latencies:
        values[runner.P95_METRIC] = 1e3 * p95(win.latencies)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def per_layer(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric from its own reader; a reader that finds
    nothing returns ``None`` and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def format_checks(checks: Dict[str, Any]) -> List[str]:
    return [f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"({'ok' if c['ok'] else 'FAIL'})" for name, c in checks.items()]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             compile_cache: bool = True,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr)
             ) -> Dict[str, Any]:
    """One run of one cell: set-up, the measured window, with ``trace``
    a traced stretch after it, then the check of what the window
    produced against the plain reference.

    The CPU tests pass ``require_tpu=False`` and ``compile_cache=False``
    to drive the rest of a run without a chip and without touching the
    process's compilation cache."""
    import jax
    devs = tpu_devices(cell.chips) if require_tpu else jax.devices()
    used = devs[:cell.chips]
    t_backend = time.perf_counter()
    if compile_cache:
        enable_compile_cache()
    compiles = CompileCounter()
    runner_mod = load_module("runners", cell.config["runner"])
    runner = runner_mod.Runner(cell.config, cell.traffic, seed, cell.chips)
    with jax.profiler.TraceAnnotation("bench.warmup"):
        runner.warmup()
    t_ready = time.perf_counter()
    setup_s = t_ready - t_process
    win = run_window(runner, seconds, compiles)
    for err in win.errors[:5]:
        log(err)
    ctx: Dict[str, Any] = {
        "setup": {"backend_init_s": t_backend - t_process,
                  "warmup_s": t_ready - t_backend, "setup_s": setup_s},
        "counters": win.counters, "trace": None}
    if trace:
        import shutil
        from trace_reduce import reduce_dir
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_queries(runner, float(cell.traffic.get("trace_delay", 0.0)),
                      float(cell.traffic["trace_seconds"]), TRACE_DIR)
        ctx["trace"] = reduce_dir(TRACE_DIR, [d.id for d in used])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    peak = memory_peak_bytes(used)
    # the program's state goes before the reference runs
    results, win.results = win.results, []
    runner.release()
    checks = runner.check(results)
    del results
    checks["window_compiles"] = {"value": win.compiles, "limit": 0,
                                 "ok": win.compiles == 0}
    checks["failed_queries"] = {"value": win.failed, "limit": 0,
                                "ok": win.failed == 0}
    correct = all(c["ok"] for c in checks.values())
    device = {**device_label(devs), "memory_peak_bytes": peak}
    line: Dict[str, Any] = {"correct": correct, "attempted": win.attempted,
                            "failed": win.failed}
    if trace:
        red = ctx["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["metrics"] = per_layer(cell, ctx)
        line["device"] = device
        line["breakdown"] = {"device_ops": red["top_ops"][:10],
                             "idle_gaps": red["idle_gaps"][:10]}
    else:
        line["metrics"] = end_to_end(cell, runner, win, setup_s)
        line["device"] = device
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    for s in format_checks(checks):
        log(s)
    return line
