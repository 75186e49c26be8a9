"""Tests for the loop-weighted HLO cost model and the memsys bridge."""
import subprocess
import sys
import os
import textwrap

import numpy as np
import pytest

from repro.core import TrafficMix
from repro.roofline.analysis import RooflineReport, memsys_bridge
from repro.roofline.hlo_parse import HloCostModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lower_hlo(body: str, devices: int = 8) -> str:
    """Compile a small sharded program in a subprocess; return HLO text."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr
    return out.stdout


class TestHloCostModel:
    @pytest.fixture(scope="class")
    def scan_hlo(self):
        return _lower_hlo("""
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, None, length=7)
            return y
        mesh = make_mesh((2, 4), ("data", "model"))
        xs = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        ws = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        lowered = jax.jit(f, in_shardings=(
            jax.NamedSharding(mesh, P("data", None)),
            jax.NamedSharding(mesh, P(None, "model")))).lower(xs, ws)
        print(lowered.compile().as_text())
        """)

    def test_loop_weighted_flops(self, scan_hlo):
        m = HloCostModel(scan_hlo)
        met = m.metrics()
        # per device: 7 iterations x 2*32*256*64 (batch/2, out 256/4)
        expect = 7 * 2 * 32 * 256 * 64
        assert met.flops == pytest.approx(expect, rel=0.01), met.flops

    def test_loop_weighted_collectives(self, scan_hlo):
        m = HloCostModel(scan_hlo)
        met = m.metrics()
        # all-gather of x shard [32, 64] f32 over model, once per iteration
        expect = 7 * 32 * 64 * 4
        assert met.collective_bytes == pytest.approx(expect, rel=0.25), \
            met.collective_bytes

    def test_bytes_reasonable(self, scan_hlo):
        m = HloCostModel(scan_hlo)
        met = m.metrics()
        # weights read (256*64 f32) + act read/write per iteration, x7;
        # must be within a small factor of the analytic expectation
        analytic = 7 * (256 * 64 + 2 * 32 * 64 + 32 * 256) * 4
        assert analytic * 0.3 < met.bytes_accessed < analytic * 6, (
            met.bytes_accessed, analytic)

    def test_trip_count_parsing(self, scan_hlo):
        m = HloCostModel(scan_hlo)
        trips = [i.trip for comp in m.comps.values() for i in comp
                 if i.opcode == "while"]
        assert 7 in trips


class TestMemsysBridge:
    def test_bridge_structure_and_ordering(self):
        rep = RooflineReport(
            arch="x", shape="train_4k", mesh="16x16", chips=256,
            hlo_flops_per_chip=1e12, hlo_bytes_per_chip=1e10,
            collective_bytes_per_chip=1e9, compute_s=5e-3, memory_s=1.2e-2,
            collective_s=2e-2, dominant="collective", model_flops=2e14,
            useful_flops_ratio=0.8, read_bytes_per_chip=7e9,
            write_bytes_per_chip=3e9)
        br = memsys_bridge(rep)
        assert 0 < br["read_fraction"] < 1
        systems = br["systems"]
        assert any("E:cxl-mem-opt" in k for k in systems)
        # UCIe-A systems must beat the LPDDR6 bus on memory term
        lp = systems["LPDDR6"]["memory_term_s"]
        e_a = systems["E:cxl-mem-opt/UCIe-A"]["memory_term_s"]
        assert e_a < lp

    def test_mix_from_byte_counts(self):
        m = TrafficMix.from_bytes(700e9, 300e9)
        assert m.read_fraction == pytest.approx(0.7)
        assert m.x + m.y == pytest.approx(100.0)
