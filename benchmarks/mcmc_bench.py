"""MCMC raw-speed benchmark: fused batched driver vs the legacy per-chain
vmap sampler, across the many-chains axis.

Measures, for num_chains in {1, 64, 1024} on a non-centered eight-schools
model widened to 64 schools (D = 66 continuous parameters):

* ``draws_per_sec``   steady-state posterior draws per wall-second (all
                      chains x kept samples / best steady run)
* ``ess_per_sec``     bulk effective sample size of ``mu`` per wall-second —
                      raw speed is worthless if the chains stop mixing
* ``cold_s``          cold-start wall time (trace + compile + first run)
* ``num_traces``      the retrace counter: MUST be 1 after a cold run plus
                      repeated same-shape reruns (the compile-once contract)

Each configuration runs in its OWN subprocess (`--worker`) so cold-compile
numbers are honest and the legacy baseline can be wall-clock budgeted: the
legacy worker gets ``max(--budget, 6x the fused worker's wall time)`` and a
timeout is treated as a *lower bound* on its steady time (the fused/legacy
speedup is then itself a lower bound, so the >= 2x assertion below stays
sound).

Assertions (exit nonzero on violation — this doubles as a CI gate):
  * every worker reports ``num_traces == 1``;
  * at the top chain count the fused driver's draws/sec is at least 2x the
    legacy sampler's (``speedup_steady >= 2``).

Usage:
  python benchmarks/mcmc_bench.py --smoke --json BENCH_mcmc.json
  python benchmarks/mcmc_bench.py            # full sizes, stdout only
"""

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

CHAIN_GRID = (1, 64, 1024)
MIN_SPEEDUP = 2.0


# ---------------------------------------------------------------------------
# worker: one (mode, chains) configuration, isolated in its own process
# ---------------------------------------------------------------------------


def run_case(mode: str, chains: int, warmup: int, samples: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro.core import primitives as P
    from repro.infer import HMC, MCMC, effective_sample_size

    # 64 synthetic "schools" (D = 66): enough likelihood work per gradient
    # that the benchmark measures sampler efficiency, not RNG/bookkeeping
    import numpy as np

    gen = np.random.default_rng(0)
    y = jnp.asarray(gen.normal(5.0, 8.0, 64).astype(np.float32))
    sigma = jnp.asarray(gen.uniform(8.0, 18.0, 64).astype(np.float32))

    def eight_schools(y, sigma):
        mu = P.sample("mu", dist.Normal(0.0, 5.0))
        log_tau = P.sample("log_tau", dist.Normal(0.0, 1.0))
        with P.plate("J", y.shape[0]):
            theta = P.sample("theta", dist.Normal(0.0, 1.0))
            P.sample("obs", dist.Normal(mu + jnp.exp(log_tau) * theta, sigma), obs=y)

    # Both samplers get the same moderate step cap (the class default is
    # 1024, which would be absurdly slow for the legacy path). The legacy
    # per-chain scan pays 2 gradients x the FULL cap every draw (its masked
    # steps still execute under vmap); the fused while_loop pays only the
    # steps actually taken (cross-chain max) — that cap-vs-actual gap is the
    # structural win this benchmark exists to measure.
    fused = mode == "fused"
    kernel = HMC(eight_schools, max_num_steps=64, adapt_trajectory_length=fused)
    mcmc = MCMC(
        kernel, num_warmup=warmup, num_samples=samples, num_chains=chains, fused=fused
    )

    t0 = time.perf_counter()
    mcmc.run(jax.random.PRNGKey(0), y, sigma)
    jax.block_until_ready(mcmc.get_samples())
    cold_s = time.perf_counter() - t0

    # steady state: fresh key + perturbed data, identical shapes -> the cached
    # executable must be reused (num_traces stays 1)
    steady_s = float("inf")
    for rep in (1, 2, 3):
        t0 = time.perf_counter()
        mcmc.run(jax.random.PRNGKey(rep), y + 1e-4 * rep, sigma)
        jax.block_until_ready(mcmc.get_samples())
        steady_s = min(steady_s, time.perf_counter() - t0)

    mu = mcmc.get_samples(group_by_chain=True)["mu"]  # (chains, samples)
    ess = float(effective_sample_size(mu))
    return {
        "mode": mode,
        "chains": chains,
        "warmup": warmup,
        "samples": samples,
        "cold_s": round(cold_s, 3),
        "steady_s": round(steady_s, 4),
        "draws_per_sec": round(chains * samples / steady_s, 1),
        "ess_per_sec": round(ess / steady_s, 1),
        "num_traces": mcmc.num_traces,
    }


# ---------------------------------------------------------------------------
# driver: spawn workers, budget the baseline, assert the contracts
# ---------------------------------------------------------------------------


def spawn_worker(mode: str, chains: int, warmup: int, samples: int, budget_s: float):
    env = os.environ.copy()
    src = str(REPO / "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    cmd = [
        sys.executable, __file__, "--worker", mode, str(chains),
        "--warmup", str(warmup), "--samples", str(samples),
    ]
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, env=env, timeout=budget_s
        )
    except subprocess.TimeoutExpired:
        # lower bound: the whole budget elapsed without finishing one cold +
        # three steady runs, so steady_s >= budget and draws/sec <= draws/budget
        return {
            "mode": mode, "chains": chains, "timed_out": True,
            "budget_s": budget_s, "steady_s": budget_s,
            "draws_per_sec": round(chains * samples / budget_s, 1),
        }, time.perf_counter() - t0
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise SystemExit(f"worker {mode}/chains={chains} failed")
    return json.loads(proc.stdout.strip().splitlines()[-1]), time.perf_counter() - t0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI sizes")
    ap.add_argument("--json", type=str, default=None, help="write results here")
    ap.add_argument("--budget", type=float, default=None,
                    help="baseline wall-clock budget floor, seconds")
    ap.add_argument("--worker", nargs=2, metavar=("MODE", "CHAINS"), default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--samples", type=int, default=None)
    args = ap.parse_args()

    # warmup is deliberately short relative to draws: draws/sec includes the
    # warmup wall clock, and warmup transitions run near the step cap while
    # the step size is still adapting (both samplers pay that equally)
    warmup = args.warmup if args.warmup is not None else (50 if args.smoke else 100)
    samples = args.samples if args.samples is not None else (500 if args.smoke else 1000)

    if args.worker is not None:
        mode, chains = args.worker[0], int(args.worker[1])
        print(json.dumps(run_case(mode, chains, warmup, samples)))
        return 0

    budget_floor = args.budget if args.budget is not None else (240.0 if args.smoke else 600.0)

    fused_rows, fused_wall_top = [], 0.0
    for chains in CHAIN_GRID:
        row, wall = spawn_worker("fused", chains, warmup, samples, budget_s=1200.0)
        print(f"fused  chains={chains:<5d} cold={row['cold_s']:.2f}s "
              f"steady={row['steady_s']:.4f}s draws/s={row['draws_per_sec']:.0f} "
              f"ESS/s={row['ess_per_sec']:.0f} traces={row['num_traces']}")
        assert row["num_traces"] == 1, (
            f"retrace regression: fused chains={chains} num_traces={row['num_traces']}"
        )
        fused_rows.append(row)
        fused_wall_top = wall

    # legacy baseline at the top chain count only — it measures the rejected
    # path, and its wall clock is budgeted off the fused worker's
    top = CHAIN_GRID[-1]
    budget = max(budget_floor, 6.0 * fused_wall_top)
    legacy, _ = spawn_worker("legacy", top, warmup, samples, budget_s=budget)
    if legacy.get("timed_out"):
        print(f"legacy chains={top}: timed out after {budget:.0f}s "
              f"(draws/s <= {legacy['draws_per_sec']:.0f}, treated as lower-bound "
              f"speedup)")
    else:
        print(f"legacy chains={top:<5d} cold={legacy['cold_s']:.2f}s "
              f"steady={legacy['steady_s']:.4f}s draws/s={legacy['draws_per_sec']:.0f}")
        assert legacy["num_traces"] == 1, "legacy retrace regression"

    fused_top = fused_rows[-1]
    speedup = fused_top["draws_per_sec"] / max(legacy["draws_per_sec"], 1e-9)
    print(f"speedup (fused vs legacy, chains={top}): {speedup:.2f}x"
          + (" (lower bound)" if legacy.get("timed_out") else ""))
    assert speedup >= MIN_SPEEDUP, (
        f"fused driver only {speedup:.2f}x over the legacy sampler at "
        f"chains={top}; the raw-speed pass requires >= {MIN_SPEEDUP}x"
    )

    results = {
        "bench": "mcmc",
        "smoke": bool(args.smoke),
        "model": "eight_schools_noncentered(J=64, D=66)",
        "warmup": warmup,
        "samples": samples,
        "fused": fused_rows,
        # baseline keys deliberately NOT gate-named (it measures the rejected
        # path); speedup_steady IS gated higher-is-better
        "legacy_baseline": {
            "chains": top,
            "steady_s_baseline": legacy["steady_s"],
            "draws_per_sec_baseline": legacy["draws_per_sec"],
            "timed_out": bool(legacy.get("timed_out", False)),
        },
        "speedup_steady": round(speedup, 2),
    }
    if args.json:
        Path(args.json).write_text(json.dumps(results, indent=2) + "\n")
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
