"""Fixtures of the chip benchmark's CPU tests: the benchmark's own cells at
a size a CPU test run holds (horizons and axis lengths cut, every other
setting as committed)."""
from __future__ import annotations

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def subprocess_env(**extra) -> dict:
    """The environment of a child Python that imports the program."""
    path = os.pathsep.join(p for p in (SRC, os.environ.get("PYTHONPATH"))
                           if p)
    return dict(os.environ, PYTHONPATH=path, **extra)


def tiny_cell(workload: str, chips: int = 1):
    import harness
    cell = harness.find_cell(harness.load_spec(), workload)
    cfg, traffic = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    if cfg["runner"] == "protocol_study":
        # 512 keeps both horizons past the periodic probes' 128 cycles
        cfg["n_flits"] = cfg["n_accesses"] = 512
        cfg["symmetric"]["mixes"] = 5
        cfg["asymmetric"]["mixes"] = 5
        cfg["asymmetric"]["perturbation"]["count"] = 2
        traffic["pool"] = 2
    else:
        cfg["n_flits"] = cfg["n_accesses"] = 256
        cfg["chunk_cells"] = 16
        cfg["backlogs"]["count"] = 3
        cfg["read_fractions"] = 5
        traffic["perturbations"] = 3
    traffic["trace_seconds"] = 0.2
    cell.config, cell.traffic, cell.chips = cfg, traffic, chips
    return cell


def run_tiny(cell, seed: int = 2 ** 31 + 7, seconds: float = 0.5,
             trace: bool = False):
    """One run of ``cell`` on the CPU, past the harness's look for a chip."""
    import time
    import harness
    return harness.run_cell(cell, seed, seconds, trace,
                            t_process=time.perf_counter(),
                            require_tpu=False, compile_cache=False,
                            log=lambda s: None)


@pytest.fixture
def fresh_programs():
    """Drop the program's compiled executables before and after a test
    that plants a fault in it."""
    from repro.core import clear_cache
    clear_cache()
    yield
    clear_cache()
