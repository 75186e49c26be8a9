"""Multi-device distribution tests (8 host CPU devices via subprocess —
the device count must be set before jax initializes, so each test body
runs in a fresh interpreter)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 900) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


class TestShardedTraining:
    def test_sharded_train_step_matches_single_device(self):
        # Requires layout-invariant RNG (jax_threefry_partitionable, jax's
        # default): with legacy threefry, init under sharded out_shardings
        # draws different embedding values than single-device init from
        # the same key (0.09 max abs diff BEFORE any train step).
        run_sub("""
        from repro.configs import get
        from repro.configs.shapes import ShapeSpec
        from repro.models import build, ShardingCtx, from_mesh
        from repro.train import (AdamW, constant_schedule, init_state,
                                 make_train_step, state_shardings,
                                 SyntheticLM)

        cfg = get("smollm-360m").reduced()
        model = build(cfg)
        opt = AdamW(learning_rate=constant_schedule(1e-3))
        src = SyntheticLM(cfg, ShapeSpec("t", 16, 8, "train"))

        # single device reference
        ctx0 = ShardingCtx()
        state0 = init_state(model, jax.random.PRNGKey(0), opt)
        step0 = jax.jit(make_train_step(model, opt, ctx0))
        s_ref, m_ref = step0(state0, src.place(src.batch_for_step(0), ctx0))

        # sharded (4 data x 2 model)
        mesh = make_mesh((4, 2), ("data", "model"))
        ctx = from_mesh(mesh)
        st_sh = state_shardings(model, ctx)
        state1 = jax.jit(lambda k: init_state(model, k, opt),
                         out_shardings=st_sh)(jax.random.PRNGKey(0))
        step1 = jax.jit(make_train_step(model, opt, ctx),
                        in_shardings=(st_sh, None), out_shardings=(st_sh, None))
        s_sh, m_sh = step1(state1, src.place(src.batch_for_step(0), ctx))

        l0, l1 = float(m_ref["loss"]), float(m_sh["loss"])
        assert abs(l0 - l1) / l0 < 2e-2, (l0, l1)
        d = max(float(jnp.max(jnp.abs(np.asarray(a, np.float32)
                                      - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree.leaves(s_ref.params),
                                jax.tree.leaves(s_sh.params)))
        assert d < 0.05, d
        print("OK sharded-vs-single", l0, l1, d)
        """)

    def test_moe_shard_map_matches_single_device(self):
        """The MoE *block* on bit-identical inputs: the shard_map
        expert-parallel path must route identically and combine to the
        same outputs as the single-device path.  (Full-model comparisons
        flip router ties through upstream bf16 reduction-order noise —
        inherent to discrete top-k, not a distribution bug.)"""
        run_sub("""
        import dataclasses
        from repro.configs import get
        from repro.models import build, ShardingCtx, from_mesh
        from repro.models.moe import moe_block, moe_schema
        from repro.models.schema import init_params
        cfg = dataclasses.replace(get("olmoe-1b-7b").reduced(),
                                  moe_capacity_factor=8.0)
        params = init_params(moe_schema(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (4, 16, cfg.d_model)).astype(jnp.bfloat16)

        ctx0 = ShardingCtx()
        out0, aux0 = moe_block(params, x, cfg, ctx0)

        mesh = make_mesh((2, 2), ("data", "model"))
        ctx = from_mesh(mesh)
        out1, aux1 = jax.jit(
            lambda p, xx: moe_block(p, xx, cfg, ctx))(params, x)
        err = float(jnp.max(jnp.abs(np.asarray(out0, np.float32)
                                    - np.asarray(out1, np.float32))))
        assert err < 0.02, err
        # per-shard aux averaging differs from global by at most Jensen gap
        assert abs(float(aux0) - float(aux1)) < 0.25
        print("OK moe shard_map", err, float(aux0), float(aux1))
        """)

    def test_elastic_checkpoint_reshard(self):
        run_sub("""
        import tempfile
        from repro.checkpoint import ckpt
        from repro.configs import get
        from repro.models import build, from_mesh, ShardingCtx
        from repro.train import (AdamW, constant_schedule, init_state,
                                 state_shardings)

        cfg = get("smollm-360m").reduced()
        model = build(cfg)
        opt = AdamW(learning_rate=constant_schedule(1e-3))

        mesh_a = make_mesh((4, 2), ("data", "model"))
        ctx_a = from_mesh(mesh_a)
        st_sh_a = state_shardings(model, ctx_a)
        state = jax.jit(lambda k: init_state(model, k, opt),
                        out_shardings=st_sh_a)(jax.random.PRNGKey(0))

        with tempfile.TemporaryDirectory() as d:
            ckpt.save(state, 0, d)
            # restore onto a DIFFERENT mesh (2x2, elastic shrink)
            mesh_b = make_mesh((2, 2), ("data", "model"))
            ctx_b = from_mesh(mesh_b)
            st_sh_b = state_shardings(model, ctx_b)
            restored, step = ckpt.restore(
                d, target=jax.eval_shape(lambda: state),
                shardings=st_sh_b)
            for x, y in zip(jax.tree.leaves(state),
                            jax.tree.leaves(restored)):
                np.testing.assert_array_equal(
                    np.asarray(x, np.float32), np.asarray(y, np.float32))
        print("OK elastic reshard")
        """)

    def test_compressed_psum_int8(self):
        run_sub("""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.train.grad_compress import compressed_psum
        mesh = make_mesh((8,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def f(xb):
            return compressed_psum(xb, "pod")

        y = jax.jit(shard_map(f, mesh=mesh, in_specs=P("pod", None),
                              out_specs=P("pod", None)))(x)
        ref = jnp.broadcast_to(x.sum(0), (8, 64))
        rel = float(jnp.max(jnp.abs(np.asarray(y)[0] - np.asarray(ref)[0]))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        assert rel < 0.05, rel
        print("OK compressed psum", rel)
        """)


class TestMeshConstruction:
    def test_production_mesh_shapes(self):
        run_sub("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh()
        assert dict(m.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("OK mesh")
        """, devices=512)
