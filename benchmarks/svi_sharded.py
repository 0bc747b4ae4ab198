"""Sharded-SVI microbench (acceptance criterion for the engine PR).

Demonstrates that the jit-compiled sharded `SVI.update` executes with NO
per-step retracing: a fresh minibatch (fresh subsample indices) every step
hits the same compiled executable, so steady-state step time is flat after
step 1 and `update_jit._cache_size()` stays at 1.

Run: PYTHONPATH=src python benchmarks/svi_sharded.py [--smoke]
(--smoke: CI-sized run — fewer steps/particles, same retrace assertions)
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import distributions as dist
from repro import optim
from repro.core import primitives as P
from repro.distributed import make_mesh
from repro.infer import SVI, AutoNormal, Trace_ELBO

N_FULL = 4096
N_BATCH = 256


def model(data):
    loc = P.sample("loc", dist.Normal(0.0, 10.0))
    scale = P.sample("scale", dist.LogNormal(0.0, 1.0))
    with P.plate("N", N_FULL, subsample_size=N_BATCH) as idx:
        P.sample("obs", dist.Normal(loc, scale), obs=data[idx])


def main(steps: int = 50, particles: int = 8, log=print):
    data = 1.5 + 0.7 * jax.random.normal(jax.random.PRNGKey(0), (N_FULL,))
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    guide = AutoNormal(model)
    svi = SVI(model, guide, optim.Adam(0.05), Trace_ELBO(num_particles=particles), mesh=mesh)
    state = svi.init(jax.random.PRNGKey(1), data)

    log(f"# sharded SVI.update: {jax.device_count()} device(s), "
        f"{particles} particles, N={N_FULL} subsample={N_BATCH}")
    log(f"{'step':>5} {'ms':>9} {'jit cache':>10}")
    times = []
    for i in range(steps):
        idx = jax.random.choice(
            jax.random.fold_in(jax.random.PRNGKey(2), i), N_FULL, (N_BATCH,), replace=False
        )
        t0 = time.perf_counter()
        state, loss = svi.update_jit(state, data, subsample={"N": idx})
        loss.block_until_ready()
        dt = (time.perf_counter() - t0) * 1e3
        times.append(dt)
        if i < 3 or i % 10 == 0:
            log(f"{i:>5} {dt:9.3f} {svi.update_jit._cache_size():>10}")

    steady = times[1:]
    log(f"step 0 (compile): {times[0]:9.3f} ms")
    log(f"steady-state:     {sum(steady)/len(steady):9.3f} ms "
        f"(min {min(steady):.3f}, max {max(steady):.3f})")
    cache = svi.update_jit._cache_size()
    log(f"compiled executables: {cache}")
    assert cache == 1, f"per-step retracing detected: cache_size={cache}"
    assert max(steady) < times[0], "steady-state should be far below compile step"
    log("OK: no per-step retracing; steady-state flat after step 1")
    return times


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    args = ap.parse_args()
    if args.smoke:
        main(steps=12, particles=2)
    else:
        main()
