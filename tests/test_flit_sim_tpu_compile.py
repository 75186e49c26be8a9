"""The streamed joint space's chunk program compiles for a TPU v5e — a
described ``v5e:2x2`` topology, no chip.

CPU runs cannot see what the chip's compiler does with a program (where
it places a scan's carried arrays, what it sinks into a loop body).  The
chunk program is compiled for one chip and for the 2x2 mesh, and its
scans are checked for per-cycle relayouts and for operands left in HBM.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs them
loads the TPU compiler.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
