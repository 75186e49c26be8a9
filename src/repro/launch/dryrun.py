import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))

# ^ MUST precede every other import: jax locks the platform and the device
# count on first initialization.  The AOT pod analysis runs on 512
# placeholder CPU devices and never takes an accelerator; this module is
# the ONLY place they exist — smoke tests and benchmarks see the real
# devices.

import argparse            # noqa: E402
import dataclasses         # noqa: E402
import json                # noqa: E402
import time                # noqa: E402
import traceback           # noqa: E402
from typing import Any, Dict, Optional   # noqa: E402

import jax                 # noqa: E402
import jax.numpy as jnp    # noqa: E402
import numpy as np         # noqa: E402

from repro.configs import arch_ids, get, SHAPES, applicable, \
    microbatches_for                                          # noqa: E402
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.models import build, from_mesh                     # noqa: E402
from repro.models.sharding import ShardingCtx                 # noqa: E402
from repro.roofline import analysis                           # noqa: E402
from repro.train.optimizer import AdamW, constant_schedule    # noqa: E402
from repro.train.train_step import (                          # noqa: E402
    init_state, make_train_step, state_shardings)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def _tree_device_bytes(avals, shardings) -> int:
    """Per-device bytes of a sharded pytree of ShapeDtypeStructs."""
    total = 0
    for aval, sh in zip(jax.tree.leaves(avals), jax.tree.leaves(
            shardings, is_leaf=lambda x: x is None
            or isinstance(x, jax.sharding.Sharding))):
        n = int(np.prod(aval.shape)) * aval.dtype.itemsize
        if sh is not None:
            n //= sh.num_devices // _replication(sh, aval.shape)
        total += n
    return total


def _replication(sharding, shape) -> int:
    spec = sharding.spec
    mesh = sharding.mesh
    used = set()
    for entry in spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            used.add(ax)
    rep = 1
    for name in mesh.axis_names:
        if name not in used:
            rep *= mesh.shape[name]
    return rep


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               sequence_parallel: bool = False,
               num_microbatches: Optional[int] = None,
               remat: Optional[bool] = None,
               donate: bool = True,
               baseline: bool = False,
               cfg_overrides: Optional[Dict[str, Any]] = None):
    """Build + lower one (arch × shape × mesh) cell.  Returns
    (lowered, ctx, meta).

    baseline=True reproduces the pre-hillclimb configuration (q-seq
    attention sharding, no gradient sharding constraints).
    cfg_overrides: dataclasses.replace overrides (e.g. ssm_chunk)."""
    cfg = get(arch)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        raise ValueError(f"skip {arch}/{shape_name}: {why}")

    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = from_mesh(mesh, sequence_parallel=sequence_parallel,
                    force_seq_attn=baseline)
    model = build(cfg)
    dp = ctx.dp_size()

    in_specs = model.input_specs(shape)
    in_shards = model.input_shardings(shape, ctx, in_specs)
    meta: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(np.prod(list(mesh.shape.values()))),
        "params": model.param_count(),
        "active_params": cfg.active_param_count(),
    }

    if shape.kind == "train":
        n_micro = (num_microbatches if num_microbatches is not None
                   else microbatches_for(cfg, shape, dp))
        meta["num_microbatches"] = n_micro
        opt = AdamW(learning_rate=constant_schedule(1e-4))
        step_fn = make_train_step(model, opt, ctx,
                                  num_microbatches=n_micro,
                                  constrain_grads=not baseline)
        state_sds = jax.eval_shape(
            lambda k: init_state(model, k, opt), jax.random.PRNGKey(0))
        st_shards = state_shardings(model, ctx)
        fn = jax.jit(step_fn,
                     in_shardings=(st_shards, in_shards),
                     out_shardings=(st_shards, None),
                     donate_argnums=(0,) if donate else ())
        lowered = fn.lower(state_sds, in_specs)
        meta["state_bytes_per_chip"] = _tree_device_bytes(
            jax.tree.leaves(state_sds), jax.tree.leaves(
                st_shards, is_leaf=lambda x: x is None or isinstance(
                    x, jax.sharding.Sharding)))
        # model flops: 6 N D per token (fwd+bwd), D = global tokens
        tokens = shape.global_batch * shape.seq_len
        meta["model_flops"] = 6.0 * cfg.active_param_count() * tokens
        return lowered, ctx, meta

    params_sds = model.abstract_params()
    p_shards = model.param_shardings(ctx)

    if shape.kind == "prefill":
        def prefill_fn(params, inputs):
            return model.prefill(params, inputs, ctx)
        fn = jax.jit(prefill_fn, in_shardings=(p_shards, in_shards))
        lowered = fn.lower(params_sds, in_specs)
        tokens = shape.global_batch * shape.seq_len
        meta["model_flops"] = 2.0 * cfg.active_param_count() * tokens
        return lowered, ctx, meta

    # decode
    cache_sds = in_specs["caches"]
    cache_shards = in_shards["caches"]

    def decode_fn(params, tokens, caches, positions):
        return model.decode_step(params, tokens, caches, positions, ctx)

    fn = jax.jit(decode_fn,
                 in_shardings=(p_shards, in_shards["tokens"], cache_shards,
                               in_shards["positions"]),
                 out_shardings=None,
                 donate_argnums=(2,) if donate else ())
    lowered = fn.lower(params_sds, in_specs["tokens"], cache_sds,
                       in_specs["positions"])
    meta["model_flops"] = 2.0 * cfg.active_param_count() \
        * shape.global_batch
    meta["cache_bytes_per_chip"] = _tree_device_bytes(
        jax.tree.leaves(cache_sds), jax.tree.leaves(
            cache_shards, is_leaf=lambda x: x is None or isinstance(
                x, jax.sharding.Sharding)))
    return lowered, ctx, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Optional[str] = None, verbose: bool = True,
             hlo_out: Optional[str] = None, **kw) -> Dict[str, Any]:
    t0 = time.time()
    lowered, ctx, meta = lower_cell(arch, shape_name, multi_pod=multi_pod,
                                    **kw)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:           # pragma: no cover
        mem, mem_info = None, {"error": str(e)}

    hlo = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)
    report = analysis.analyze(
        arch, shape_name, meta["mesh"], meta["chips"], cost, hlo,
        meta["model_flops"],
        peak_memory_bytes=float(mem_info.get("temp_size_in_bytes", 0)))
    bridge = analysis.memsys_bridge(report)

    result = {
        **meta,
        "lower_s": t_lower, "compile_s": t_compile,
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_info,
        "roofline": report.to_json(),
        "memsys_bridge": bridge,
        "hlo_bytes": len(hlo),
    }
    if verbose:
        print(f"== {arch} × {shape_name} × {meta['mesh']} "
              f"({meta['chips']} chips) ==")
        print(f"   lower {t_lower:.1f}s  compile {t_compile:.1f}s")
        print(f"   memory_analysis: {mem_info}")
        print(f"   cost_analysis: flops={cost.get('flops', 0):.3e} "
              f"bytes={cost.get('bytes accessed', 0):.3e}")
        r = report
        print(f"   roofline: compute={r.compute_s*1e3:.2f}ms "
              f"memory={r.memory_s*1e3:.2f}ms "
              f"collective={r.collective_s*1e3:.2f}ms "
              f"-> dominant={r.dominant} "
              f"useful_flops={r.useful_flops_ratio:.2f}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{meta['mesh']}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch × shape) cell")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--sequence-parallel", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in arch_ids():
            cfg = get(arch)
            for shape_name, shape in SHAPES.items():
                ok, why = applicable(cfg, shape)
                if ok:
                    cells.append((arch, shape_name))
                else:
                    print(f"SKIP {arch} × {shape_name}: {why}")
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    results = []
    for arch, shape_name in cells:
        try:
            results.append(run_cell(
                arch, shape_name, multi_pod=args.multi_pod,
                out_dir=args.out,
                num_microbatches=args.microbatches,
                sequence_parallel=args.sequence_parallel,
                remat=False if args.no_remat else None))
        except Exception:
            traceback.print_exc()
            failures.append((arch, shape_name))
    if results:
        # one batched [configs x catalog x mix-grid x shoreline] evaluation
        # over every compiled cell: each workload's design-space frontier
        reports = {
            f"{r['arch']}__{r['shape']}__{r['mesh']}":
                analysis.RooflineReport(**r["roofline"])
            for r in results}
        ds = analysis.bridge_design_space(reports)
        if args.all:
            # persist the aggregate only for full sweeps — a later
            # single-cell refresh must not clobber the all-cells space.
            # The artifact carries the joint (mix x backlog x shoreline)
            # analytic-vs-simulated frontier alongside the per-workload
            # bridge, so downstream consumers see where the cycle-level
            # simulation overrules the closed forms.
            from repro.core.space import DesignSpace, joint_frontier
            ds["joint_frontier"] = joint_frontier()
            # the serving-trace frontier rides along: which memory
            # approach wins at which (model, QPS) point, from synthetic
            # serving traces evaluated through the trace axis
            ds["serving_frontier"] = DesignSpace.serving_frontier()
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, analysis.DESIGN_SPACE_JSON),
                      "w") as f:
                json.dump(ds, f, indent=1)
        for name, w in ds["workloads"].items():
            print(f"frontier {name}: best={w['best']} ({w['mix']}) "
                  f"shoreline_sensitive={w['shoreline_sensitive']}")
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print(f"dry-run OK: {len(cells)} cells")


if __name__ == "__main__":
    main()
