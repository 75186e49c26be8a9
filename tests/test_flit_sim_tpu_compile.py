"""The four ``flit_sim`` Pallas launches compile for a TPU v5e at the
tiles ``tile_for`` picks — a described ``v5e:2x2`` topology, no chip.

Interpret-mode tests cannot see what the chip's compiler refuses (vector
iotas that are not integer, primitives with no Mosaic lowering, scoped
VMEM).  Each case compiles one launch with ``interpret=False`` and checks
that a Mosaic kernel (``tpu_custom_call``) is in the compiled program.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs them
loads the TPU compiler.
"""
import functools

import jax
import jax.numpy as jnp
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
