"""SMC engine benchmark: sweep throughput, estimator quality, and the
sharding contract, across the particle-count axis.

Measures, for num_particles in {64, 4096, 65536} on a linear-Gaussian
state-space model (the model with a closed-form Kalman answer, so the
log-marginal-likelihood estimate can be scored against truth):

* ``steps_per_sec``   steady-state filter steps per wall-second (T steps /
                      best steady sweep; the whole sweep is ONE compiled
                      call, so this is the `lax.scan` body throughput)
* ``log_z_var``       variance of log Ẑ across repeated sweeps — the
                      estimator-quality axis: more particles must buy lower
                      variance, and the mean must sit near the exact answer
* ``cold_s``          cold-start wall time (trace + compile + first sweep)
* ``num_traces``      the retrace counter: MUST be 1 after a cold sweep plus
                      repeated same-shape re-runs (the compile-once contract)

Also asserts the sharding contract at every size: a sweep with the particle
axis constrained onto a 1-device mesh is bit-for-bit identical to the plain
vectorized sweep (same contract `benchmarks/mcmc_chains.py` pins for
chains, here for particles).

Usage:
  python benchmarks/smc_bench.py --smoke --json BENCH_smc.json
  python benchmarks/smc_bench.py            # full sizes, stdout only
"""

import argparse
import json
import math
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))

PARTICLE_GRID = (64, 4096, 65536)
REPEATS = 5

# SSM coefficients (shared with the exact Kalman scorer below)
A, S_TRANS, S_OBS = 0.9, 0.3, 0.5


def exact_log_z(ys) -> float:
    """Closed-form log p(y_0..y_{T-1}) for the scalar linear-Gaussian SSM:
    x_0 ~ N(0,1), x_t ~ N(A x_{t-1}, S_TRANS), y_t ~ N(x_t, S_OBS)."""
    m, p = 0.0, 1.0
    ll = 0.0
    for y in ys:
        s = p + S_OBS**2
        ll += -0.5 * (math.log(2 * math.pi * s) + (float(y) - m) ** 2 / s)
        k = p / s
        m = m + k * (float(y) - m)
        p = (1.0 - k) * p
        m, p = A * m, A * A * p + S_TRANS**2
    return ll


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI sizes")
    ap.add_argument("--json", type=str, default=None, help="write results here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import distributions as dist
    from repro.core import primitives as P
    from repro.distributed import make_mesh
    from repro.infer import SMC

    T = 16 if args.smoke else 64
    grid = (64, 1024, 4096) if args.smoke else PARTICLE_GRID

    def model_init(y):
        x = P.sample("x", dist.Normal(0.0, 1.0))
        P.sample("y", dist.Normal(x, S_OBS), obs=y)
        return {"x": x}

    def model_step(carry, y):
        x = P.sample("x", dist.Normal(A * carry["x"], S_TRANS))
        P.sample("y", dist.Normal(x, S_OBS), obs=y)
        return {"x": x}

    # one fixed observation sequence, simulated from the model itself
    gen = np.random.default_rng(0)
    xs_true = [gen.normal(0.0, 1.0)]
    for _ in range(T - 1):
        xs_true.append(A * xs_true[-1] + gen.normal(0.0, S_TRANS))
    ys = jnp.asarray(
        [x + gen.normal(0.0, S_OBS) for x in xs_true], dtype=jnp.float32
    )
    log_z_exact = exact_log_z(ys)
    print(f"T={T} observations, exact log Z = {log_z_exact:.4f}")

    mesh = make_mesh((jax.device_count(),), ("data",))
    rows = []
    for n in grid:
        smc = SMC(model_init, model_step, num_particles=n)

        t0 = time.perf_counter()
        smc.run(jax.random.PRNGKey(0), ys)
        jax.block_until_ready(smc.log_evidence())
        cold_s = time.perf_counter() - t0

        # steady state: fresh keys, identical shapes -> the cached executable
        # must be reused (num_traces stays 1); log Z across repeats scores
        # the estimator
        steady_s, log_zs = float("inf"), []
        for rep in range(1, REPEATS + 1):
            t0 = time.perf_counter()
            smc.run(jax.random.PRNGKey(rep), ys)
            log_zs.append(float(jax.block_until_ready(smc.log_evidence())))
            steady_s = min(steady_s, time.perf_counter() - t0)
        assert smc.num_traces == 1, (
            f"retrace regression: N={n} num_traces={smc.num_traces}"
        )

        # sharding contract: particle axis on a mesh == plain vmap,
        # bit-for-bit when the mesh degenerates to one device
        sharded = SMC(model_init, model_step, num_particles=n, mesh=mesh)
        sharded.run(jax.random.PRNGKey(1), ys)
        bit_identical = None
        if jax.device_count() == 1:
            bit_identical = bool(
                jnp.array_equal(sharded.log_weights, _rerun(smc, ys))
                and float(sharded.log_evidence()) == log_zs[0]
            )
            assert bit_identical, (
                f"sharded sweep diverged from vectorized at N={n} "
                "on a 1-device mesh"
            )

        lz_mean = sum(log_zs) / len(log_zs)
        lz_var = sum((v - lz_mean) ** 2 for v in log_zs) / len(log_zs)
        row = {
            "bench": "smc",
            "particles": n,
            "T": T,
            "cold_s": round(cold_s, 3),
            "steady_s": round(steady_s, 4),
            "steps_per_sec": round(T / steady_s, 1),
            "log_z_mean": round(lz_mean, 4),
            "log_z_var": round(lz_var, 5),
            "log_z_exact": round(log_z_exact, 4),
            "num_traces": smc.num_traces,
            "sharded_bit_identical": bit_identical,
        }
        rows.append(row)
        print(
            f"N={n:<6d} cold={row['cold_s']:.2f}s steady={row['steady_s']:.4f}s "
            f"steps/s={row['steps_per_sec']:.0f} "
            f"logZ={lz_mean:.3f}±{math.sqrt(lz_var):.3f} "
            f"(exact {log_z_exact:.3f}) traces={row['num_traces']}"
        )

    # estimator sanity: variance shrinks (weakly) from the smallest to the
    # largest population, and the biggest population lands near truth
    assert rows[-1]["log_z_var"] <= rows[0]["log_z_var"] + 0.05, (
        "log Z variance did not shrink with particle count: "
        f"{[r['log_z_var'] for r in rows]}"
    )
    sigma = math.sqrt(rows[-1]["log_z_var"]) + 1e-3
    assert abs(rows[-1]["log_z_mean"] - log_z_exact) < max(5 * sigma, 0.5), (
        f"log Z biased at N={rows[-1]['particles']}: "
        f"{rows[-1]['log_z_mean']} vs exact {log_z_exact}"
    )

    results = {
        "bench": "smc",
        "smoke": bool(args.smoke),
        "model": f"linear_gaussian_ssm(A={A}, s_trans={S_TRANS}, s_obs={S_OBS})",
        "T": T,
        "repeats": REPEATS,
        "log_z_exact": round(log_z_exact, 4),
        "sweeps": rows,
    }
    if args.json:
        Path(args.json).write_text(json.dumps(results, indent=2) + "\n")
        print(f"wrote {args.json}")
    return 0


def _rerun(smc, ys):
    """Re-run the vectorized engine with the sharded comparison's key and
    return the final log-weights (keeps the parity check key-aligned)."""
    import jax

    smc.run(jax.random.PRNGKey(1), ys)
    return smc.log_weights


if __name__ == "__main__":
    sys.exit(main())
