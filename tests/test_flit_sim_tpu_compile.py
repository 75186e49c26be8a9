"""The four ``flit_sim`` Pallas launches compile for a TPU v5e at the
tiles ``tile_for`` picks — a described ``v5e:2x2`` topology, no chip.

Interpret-mode tests cannot see what the chip's compiler refuses (vector
iotas that are not integer, primitives with no Mosaic lowering, scoped
VMEM).  Each case compiles one launch with ``interpret=False`` and checks
that a Mosaic kernel (``tpu_custom_call``) is in the compiled program.
The streamed joint space's chunk program is compiled for one chip and
for the 2x2 mesh too, and its scans are checked for per-cycle relayouts
and for operands left in HBM.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs them
loads the TPU compiler.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flit_sim import kernel as fs_kernel
from repro.kernels.flit_sim import ops as fs_ops

#: adaptive chunk length of the default SimConfig
CHUNK = 128
#: default horizons of DesignSpace
N_FLITS, N_ACCESSES = 2048, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _launch(name: str, one_chip):
    """(launch fn, abstract args) at the real tile of ``name``."""
    cap = {"asymmetric_periodic": fs_kernel.ASYM_PERIODIC_MAX_TILE,
           "symmetric_periodic": fs_kernel.SYM_PERIODIC_MAX_TILE
           }.get(name, fs_kernel.MAX_TILE)
    # two tiles of cells, so the grid steps over the cell axis
    tile, cells = fs_ops.tile_for(2 * cap, cap)
    assert tile == cap

    def rows(n, cols=cells):
        return jax.ShapeDtypeStruct((n, cols), jnp.float32,
                                    sharding=one_chip)

    scal = rows(1, fs_ops.SCAL_COLS)
    common = dict(tile=tile, cells=cells, interpret=False)
    return {
        "symmetric_chunk": (
            functools.partial(fs_ops.symmetric_chunk_launch, chunk=CHUNK,
                              **common),
            (rows(fs_ops.SYM_ROWS), rows(fs_ops.SYM_ROWS),
             rows(fs_ops.SYM_ROWS), scal)),
        "pipelining_chunk": (
            functools.partial(fs_ops.pipelining_chunk_launch, chunk=CHUNK,
                              **common),
            (rows(fs_ops.PIPE_ROWS), rows(fs_ops.PIPE_ROWS),
             rows(fs_ops.ASYM_ROWS), scal)),
        "asymmetric_periodic": (
            functools.partial(fs_ops.asymmetric_periodic_launch,
                              n_accesses=N_ACCESSES, **common),
            (rows(fs_ops.ASYM_ROWS),)),
        "symmetric_periodic": (
            functools.partial(fs_ops.symmetric_periodic_launch,
                              n_flits=N_FLITS, **common),
            (rows(fs_ops.SYM_ROWS),)),
    }[name]


@pytest.mark.parametrize("name", ["symmetric_chunk", "pipelining_chunk",
                                  "asymmetric_periodic",
                                  "symmetric_periodic"])
def test_launch_compiles_to_mosaic_kernel(name, one_chip):
    fn, args = _launch(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the joint space's stream: 2500 perturbations, 4096 cells a device
STREAM_PERTS, STREAM_CHUNK = 2500, 4096


def _while_bodies(hlo: str):
    """The text of every ``while`` body computation in ``hlo``."""
    names = set(re.findall(r"\bbody=(%[\w.\-]+)", hlo))
    return [b for b in re.findall(r"\n(%[\w.\-]+ \(.*?\n})", hlo, re.S)
            if b.split(" ", 1)[0] in names]


@pytest.mark.parametrize("devices", [1, 4])
def test_stream_chunk_scans_carry_whole_operands(devices, topo):
    """The streamed joint space's chunk program, compiled for v5e: each
    fixed-horizon scan carries the cells' gathered parameter rows and
    repeated mix and backlog whole, so no broadcast or reshape runs
    inside a scan body (an unbarriered repeat is sunk into the loop and
    redone every cycle, ten times the scan's time on the chip), and every
    carried array sits in VMEM (host-fed per-cell operands left three of
    the symmetric scan's parameters in HBM, 3.5 times its time a cycle)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import flitsim, streaming

    keys = tuple(flitsim.SIMULATORS)
    mesh = Mesh(np.asarray(topo.devices[:devices]), ("chunks",))
    rep = NamedSharding(mesh, PartitionSpec())
    per_cell = NamedSharding(mesh, PartitionSpec("chunks"))

    def table(cls, p_fam):
        n = len(dataclasses.fields(cls))
        return cls(*[jax.ShapeDtypeStruct((STREAM_PERTS * p_fam,),
                                          jnp.float32, sharding=rep)
                     for _ in range(n)])

    step = devices * STREAM_CHUNK
    args = (table(flitsim.SymmetricFlitParams,
                  len(flitsim.SYMMETRIC_PARAMS)),
            table(flitsim.AsymmetricLaneParams,
                  len(flitsim.ASYMMETRIC_PARAMS)),
            jax.ShapeDtypeStruct((4,), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((step, 2), jnp.int32, sharding=per_cell),
            jax.ShapeDtypeStruct((step, 3), jnp.float32, sharding=per_cell))
    fn = streaming._sim_chunk_fn(mesh, keys, STREAM_CHUNK, N_FLITS,
                                 N_ACCESSES)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    bodies = _while_bodies(hlo)
    assert len(bodies) >= 2            # the symmetric and asymmetric scans
    for body in bodies:
        assert not re.search(r"= \S+ (broadcast|reshape)\(", body), \
            body.split("\n", 1)[0][:200]
    # every array a scan carries lives in VMEM (memory space S(1)): an
    # operand left in HBM is read from there on every cycle
    loops = [ln for ln in hlo.split("\n") if " while(" in ln]
    assert len(loops) >= 2
    for ln in loops:
        carried = re.findall(r"f32\[\d+\]\{[^}]*\}", ln.split(" while(")[0])
        assert carried and all("S(1)" in c for c in carried), ln[:300]
