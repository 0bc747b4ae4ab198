"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, dump memory/cost analysis and roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out dryrun.json

The 512 placeholder host devices exist ONLY here (the env-var assignment
below must run before any jax import — do not import this module from
tests)."""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # noqa: E402 — before any jax import

import argparse
import json
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import configs
from ..distributed.sharding import (
    activation_sharding_scope,
    batch_shardings,
    cache_shardings,
    param_shardings,
    replicated,
)
from ..models import (
    init_cache,
    init_params,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from ..optim import Adam
from .hlo_cost import analyze_hlo
from .mesh import make_production_mesh
from .roofline import Roofline, model_flops

# the production mesh is a v5e pod; the placeholder CPU devices only lower
TARGET_DEVICE_KIND = "TPU v5 lite"


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(cfg, shape_spec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    B, S = shape_spec.global_batch, shape_spec.seq_len
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.modality in ("audio", "vlm"):
        emb = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        batch = {"inputs": emb, "targets": tok}
        one = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.dtype(cfg.compute_dtype))
    else:
        batch = {"tokens": tok, "targets": tok}
        one = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    return {"batch": batch, "one_token": one}


def abstract_params(cfg):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def abstract_cache(cfg, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


def _analyze(lowered, compiled, *, label: str, verbose: bool = True) -> Tuple[dict, dict]:
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}
    if verbose:
        print(f"  [{label}] memory_analysis: {mem_d}")
        print(f"  [{label}] cost_analysis: flops={cost.get('flops')} "
              f"bytes={cost.get('bytes accessed')}")
    return cost, mem_d


def run_cell(
    arch: str,
    shape: str,
    *,
    multi_pod: bool = False,
    verbose: bool = True,
    hlo_dir: Optional[str] = None,
    cfg_override=None,
    baseline: bool = False,
) -> dict:
    """Lower+compile one (arch, shape, mesh) cell; return the record.
    baseline=True reproduces the paper-faithful pre-hillclimb configuration
    (dots remat policy, XLA-default attention VJP, rank-sharded MLA cache,
    unpinned prefill cache shardings) for the §Perf before/after table."""
    spec = configs.SHAPES[shape]
    cfg = cfg_override or configs.get_config(arch)
    if baseline:
        cfg = cfg.replace(remat_policy="dots", attn_impl="blockwise", seq_parallel=False)
    elif cfg_override is None:
        # beyond-paper default (§Perf). Fine-grained MoE (>=64 experts) is
        # excluded: S-sharded residuals inflate its dispatch all-to-alls
        # more than they save in HBM (measured: deepseek-v2-lite train
        # frac 0.049 -> 0.039 with SP on; see EXPERIMENTS §Perf).
        cfg = cfg.replace(seq_parallel=not (cfg.moe and cfg.n_experts >= 64))
    mla_mode = "rank" if baseline else "seq"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mesh_name = "multi" if multi_pod else "single"
    t0 = time.time()

    params_a = abstract_params(cfg)
    p_shard = param_shardings(params_a, mesh)

    if spec.kind == "train":
        optimizer = Adam(3e-4)
        state_a = jax.eval_shape(optimizer.init, params_a)
        # optimizer moments shard exactly like params; step counter replicated
        from ..optim.optimizers import OptState

        s_shard = OptState(replicated(mesh), p_shard, p_shard, p_shard)
        batch_a = input_specs(cfg, spec)["batch"]
        b_shard = batch_shardings(batch_a, mesh)
        step = make_train_step(cfg, optimizer)
        jitted = jax.jit(
            step,
            in_shardings=(s_shard, b_shard),
            out_shardings=(s_shard, replicated(mesh)),
            donate_argnums=(0,),
        )
        with mesh, activation_sharding_scope(mesh):
            lowered = jitted.lower(state_a, batch_a)
    elif spec.kind == "prefill":
        batch_a = input_specs(cfg, spec)
        tokens_a = batch_a["batch"].get("tokens", batch_a["batch"].get("inputs"))
        t_shard = batch_shardings(tokens_a, mesh)
        step = make_prefill_step(cfg)
        # out_shardings MUST pin the returned cache: leaving it unspecified
        # lets XLA replicate the KV cache — a ~TB-scale all-gather
        # (the deepseek-coder prefill hillclimb finding, EXPERIMENTS §Perf)
        if baseline:
            jitted = jax.jit(step, in_shardings=(p_shard, t_shard))
        else:
            cache_a = jax.eval_shape(step, params_a, tokens_a)[1]
            c_shard = cache_shardings(cache_a, cfg, mesh, mla_mode=mla_mode)
            last_shard = batch_shardings(
                jax.ShapeDtypeStruct((spec.global_batch, cfg.vocab), jnp.float32), mesh
            )
            jitted = jax.jit(step, in_shardings=(p_shard, t_shard),
                             out_shardings=(last_shard, c_shard))
        with mesh, activation_sharding_scope(mesh):
            lowered = jitted.lower(params_a, tokens_a)
    else:  # decode
        cache_a = abstract_cache(cfg, spec.global_batch, spec.seq_len)
        c_shard = cache_shardings(cache_a, cfg, mesh, mla_mode=mla_mode)
        one_a = input_specs(cfg, spec)["one_token"]
        o_shard = batch_shardings(one_a, mesh)
        rng_a = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step = make_decode_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, c_shard, o_shard, replicated(mesh)),
            out_shardings=(
                batch_shardings(jax.ShapeDtypeStruct((spec.global_batch,), jnp.int32), mesh),
                c_shard,
                batch_shardings(
                    jax.ShapeDtypeStruct((spec.global_batch, cfg.vocab), jnp.float32), mesh
                ),
            ),
            donate_argnums=(1,),
        )
        with mesh, activation_sharding_scope(mesh):
            lowered = jitted.lower(params_a, cache_a, one_a, rng_a)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost, mem_d = _analyze(lowered, compiled, label=f"{arch}/{shape}/{mesh_name}",
                           verbose=verbose)
    hlo = compiled.as_text()
    # trip-count-aware walker (XLA's cost_analysis counts while bodies once)
    hc = analyze_hlo(hlo)
    coll = {k: v for k, v in hc.collectives.items()}
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        with open(os.path.join(hlo_dir, f"{arch}_{shape}_{mesh_name}.hlo"), "w") as f:
            f.write(hlo)

    rf = Roofline(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        device_kind=TARGET_DEVICE_KIND,
        chips=chips,
        flops_per_device=hc.flops,
        bytes_per_device=hc.bytes,
        collective_bytes_per_device=hc.collective_bytes,
        model_flops=model_flops(cfg, spec.seq_len, spec.global_batch, spec.kind),
    )
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "variant": "baseline" if baseline else "optimized",
        "kind": spec.kind,
        "chips": chips,
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
        "memory": mem_d,
        "collectives": coll,
        "roofline": rf.to_dict(),
    }
    if verbose:
        print(f"  [{arch}/{shape}/{mesh_name}] collectives: "
              f"{ {k: f'{v/1e9:.2f}GB' for k, v in coll.items() if v} }")
        print(f"  [{arch}/{shape}/{mesh_name}] roofline: "
              f"compute={rf.t_compute*1e3:.1f}ms memory={rf.t_memory*1e3:.1f}ms "
              f"collective={rf.t_collective*1e3:.1f}ms -> {rf.bottleneck}-bound, "
              f"useful={rf.useful_flops_ratio:.2f} frac={rf.roofline_fraction:.2f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful pre-hillclimb configuration")
    args = ap.parse_args(argv)

    if args.all:
        cell_list = configs.cells()
    else:
        archs = [args.arch] if args.arch else list(configs.ARCHS)
        shapes = [args.shape] if args.shape else list(configs.SHAPES)
        cell_list = [
            (a, s) for a in archs for s in shapes if configs.shape_applicable(a, s)
        ]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    records = []
    failures = 0
    for arch, shape in cell_list:
        for mp in meshes:
            try:
                records.append(run_cell(arch, shape, multi_pod=mp,
                                        hlo_dir=args.hlo_dir, baseline=args.baseline))
            except Exception as e:
                failures += 1
                print(f"FAIL {arch}/{shape}/{'multi' if mp else 'single'}: {e!r}")
                records.append({"arch": arch, "shape": shape,
                                "mesh": "multi" if mp else "single",
                                "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out} ({len(records)} records, {failures} failures)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
