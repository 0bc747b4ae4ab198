"""Enumeration VE microbench: pairwise greedy elimination vs the planned
contraction path (acceptance criterion for the contraction-planner PR).

Three levels:

1. Contraction level — a synthetic hidden-Markov chain of T binary K x K
   log-factors plus unary observation factors, contracted by
   `contract_log_factors` with ``dispatch="pairwise"`` (legacy greedy path:
   O(T) sequential pairwise logsumexp eliminations, O(T^2) trace-time Python,
   and an XLA graph whose compile time explodes superlinearly in T) vs
   ``dispatch="auto"`` (cost-based contraction planner: short chains stay on
   the bit-identical unrolled path, long chains roll through a plan-level
   `lax.scan` whose traced graph is O(1) in T). At T=512, K=32 the pairwise
   path does not finish *compiling* inside any sane budget, so it runs in a
   budgeted subprocess and is reported as a lower bound when it times out.

2. Plan-cache level — a second, freshly jitted contraction of the same
   structure must be served from the plan cache (hits > 0, ~zero planning
   time): the plan is a compiler artifact keyed on the factor graph's
   structural fingerprint, not rediscovered per trace.

3. Model level — a real enumerated HMM and GMM driven through
   `TraceEnum_ELBO` + `SVI.update_jit`: per-step wall time and the retrace
   counter, which must stay at 1 (fresh same-shape data must never recompile).

Writes a machine-readable BENCH_enum.json (wall-time per step, retrace
counters, plan-cache stats, GMM/HMM sizes) and exits nonzero on any retrace
regression, if auto fails to hold steady-state parity with pairwise at
matched T, if the T=512 cold compile misses its budget, or if the plan cache
misses on a repeated structure (reference backend, CPU).

Run: PYTHONPATH=src python benchmarks/enum_ve.py [--smoke] [--json PATH]
"""

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# contraction-level chain benchmark
# ---------------------------------------------------------------------------


def chain_inputs(T: int, K: int, seed: int = 0):
    import jax

    key = jax.random.PRNGKey(seed)
    trans = jax.random.normal(key, (T, K, K))
    obs = jax.random.normal(jax.random.fold_in(key, 1), (T, K))
    prior = jax.random.normal(jax.random.fold_in(key, 2), (K,))
    return trans, obs, prior


def build_chain_factors(T: int, K: int, trans, obs, prior):
    """Factors in `_collect_factors` layout: z_t lives on enum dim -(t+1), so
    the transition factor t -> t+1 is right-aligned with rank t+1 (the deep
    negative dims are what the enum messenger allocates for a T-step chain)."""
    factors = [(frozenset(), prior, None)]
    for t in range(1, T + 1):
        factors.append(
            (frozenset(), trans[t - 1].reshape((K, K) + (1,) * (t - 1)), None)
        )
        factors.append(
            (frozenset(), obs[t - 1].reshape((K,) + (1,) * t), None)
        )
    return factors, frozenset(-(t + 1) for t in range(T + 1))


def time_contract(T: int, K: int, dispatch: str, reps: int = 10):
    import jax
    import jax.numpy as jnp

    from repro.infer.traceenum_elbo import contract_log_factors

    trans, obs, prior = chain_inputs(T, K)
    pool = build_chain_factors(T, K, trans, obs, prior)[1]

    @jax.jit
    def run(trans, obs, prior):
        factors, _ = build_chain_factors(T, K, trans, obs, prior)
        return contract_log_factors(factors, {}, pool, dispatch=dispatch)

    t0 = time.perf_counter()
    r = run(trans, obs, prior)
    jax.block_until_ready(r)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        r = run(trans, obs, prior)
    jax.block_until_ready(r)
    return {
        "T": T,
        "K": K,
        "dispatch": dispatch,
        "cold_s": round(cold_s, 3),  # trace + compile + first step
        "steady_ms": round((time.perf_counter() - t0) / reps * 1e3, 3),
        "log_z": round(float(jnp.squeeze(r)), 4),
    }


def _worker(args, timeout=None):
    """Run this script's worker entry in a child process and return its last
    stdout line as JSON (earlier lines are progress, echoed). The parent never
    imports JAX: a device belongs to one process, so every measurement runs in
    a child that starts after the previous one has exited."""
    cmd = [sys.executable, str(Path(__file__).resolve())] + args
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO / 'src'}{os.pathsep}{env.get('PYTHONPATH', '')}"
    # inherit the parent's platform untouched: both sides of the winner
    # comparison must run on the same device (ci.sh exports JAX_PLATFORMS=cpu)
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, check=True, env=env
    )
    lines = out.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line)
    return json.loads(lines[-1])


def time_contract_budgeted(T: int, K: int, dispatch: str, budget_s: float):
    """Run `time_contract` in a subprocess with a wall-clock budget: the
    pairwise path at T=512 spends its time inside XLA compilation, which
    cannot be interrupted cooperatively."""
    try:
        return _worker(["--worker", str(T), str(K), dispatch], timeout=budget_s)
    except subprocess.TimeoutExpired:
        return {"T": T, "K": K, "dispatch": dispatch, "timed_out": True, "budget_s": budget_s}
    except subprocess.CalledProcessError as e:
        return {"T": T, "K": K, "dispatch": dispatch, "failed": True, "stderr": e.stderr[-2000:]}


# ---------------------------------------------------------------------------
# model-level: real enumerated GMM / HMM through TraceEnum_ELBO
# ---------------------------------------------------------------------------


def model_stage(hmm_T: int, hmm_K: int, gmm_N: int, steps: int, log=print):
    import jax
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro import optim
    from repro.core import handlers
    from repro.core import primitives as P
    from repro.infer import SVI, TraceEnum_ELBO, config, infer_discrete

    out = {}

    # -- GMM: global mixture weights, enumerated assignment under a plate ----
    weights = jnp.asarray([0.4, 0.6])
    data = jnp.concatenate(
        [
            -1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(0), (gmm_N // 2,)),
            2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(1), (gmm_N - gmm_N // 2,)),
        ]
    )

    def gmm(data):
        locs_p = P.param("locs", jnp.asarray([-0.5, 0.5]))
        with P.plate("N", data.shape[0]):
            z = P.sample("z", dist.Categorical(weights), infer={"enumerate": "parallel"})
            P.sample("obs", dist.Normal(locs_p[z], 0.5), obs=data)

    elbo = TraceEnum_ELBO()
    svi = SVI(gmm, lambda data: None, optim.Adam(0.05), elbo)
    state = svi.init(jax.random.PRNGKey(0), data)
    elbo.num_traces = 0
    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        state, loss = svi.update_jit(state, data + 1e-4 * i)  # fresh same-shape data
        loss.block_until_ready()
        times.append(time.perf_counter() - t0)
    out["gmm"] = {
        "N": gmm_N,
        "K": 2,
        "steps": steps,
        "step_ms": round(min(times) * 1e3, 3),
        "num_traces": elbo.num_traces,
    }
    assert elbo.num_traces == 1, f"GMM retraced: {elbo.num_traces} traces in {steps} steps"

    # -- HMM: enumerated Markov chain (the chain-dispatch consumer) ----------
    trans_p = jnp.asarray(
        jax.random.dirichlet(jax.random.PRNGKey(2), jnp.ones(hmm_K), (hmm_K,))
    )
    init_p = jnp.ones(hmm_K) / hmm_K
    locs_h = jnp.linspace(-2.0, 2.0, hmm_K)
    obs_seq = jax.random.normal(jax.random.PRNGKey(3), (hmm_T,))

    @config(enumerate=True)
    def hmm(obs_seq):
        scale = P.param("scale", jnp.asarray(1.0))
        z = P.sample("z_0", dist.Categorical(init_p))
        P.sample("x_0", dist.Normal(locs_h[z], scale), obs=obs_seq[0])
        for t in range(1, hmm_T):
            z = P.sample(f"z_{t}", dist.Categorical(trans_p[z]))
            P.sample(f"x_{t}", dist.Normal(locs_h[z], scale), obs=obs_seq[t])

    elbo_h = TraceEnum_ELBO()
    svi_h = SVI(hmm, lambda obs_seq: None, optim.Adam(0.01), elbo_h)
    state = svi_h.init(jax.random.PRNGKey(4), obs_seq)
    elbo_h.num_traces = 0
    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        state, loss = svi_h.update_jit(state, obs_seq + 1e-4 * i)
        loss.block_until_ready()
        times.append(time.perf_counter() - t0)
    assert elbo_h.num_traces == 1, f"HMM retraced: {elbo_h.num_traces} traces in {steps} steps"

    # Viterbi decode (max-product semiring through the same dispatch)
    t0 = time.perf_counter()
    dec = infer_discrete(hmm, temperature=0, rng_key=jax.random.PRNGKey(5))
    tr = handlers.trace(handlers.seed(dec, jax.random.PRNGKey(6))).get_trace(obs_seq)
    path = [int(tr[f"z_{t}"]["value"]) for t in range(hmm_T)]
    out["hmm"] = {
        "T": hmm_T,
        "K": hmm_K,
        "steps": steps,
        "step_ms": round(min(times) * 1e3, 3),
        "num_traces": elbo_h.num_traces,
        "viterbi_s": round(time.perf_counter() - t0, 3),
        "viterbi_states_visited": len(set(path)),
    }
    log(f"  gmm: {out['gmm']}")
    log(f"  hmm: {out['hmm']}")
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


BIG_T, BIG_K = 512, 32


def planned_stage(smoke: bool) -> dict:
    """Everything but the budgeted pairwise T=512 attempt, in one worker:
    matched-T parity, the planned T=512 chain, the plan cache, the models."""
    import jax

    from repro.infer import clear_plan_cache, plan_cache_stats
    from repro.launch.compile_cache import compilation_cache_stats

    matched = [16, 64] if smoke else [16, 64, 128]
    clear_plan_cache()
    results = {
        "bench": "enum_ve",
        "jax": jax.__version__,
        "jax_backend": jax.default_backend(),
        "kernel_backend_env": os.environ.get("REPRO_KERNEL_BACKEND", "auto (reference off-TPU)"),
        "smoke": bool(smoke),
        "chain": [],
    }

    print(f"# contraction level: pairwise vs planned dispatch (K={BIG_K})")
    print(f"{'T':>5} {'dispatch':>9} {'cold_s':>9} {'steady_ms':>10}")
    steady = {}
    for T in matched:
        for dispatch in ("pairwise", "auto"):
            r = time_contract(T, BIG_K, dispatch)
            results["chain"].append(r)
            steady[(T, dispatch)] = r["steady_ms"]
            print(f"{T:>5} {dispatch:>9} {r['cold_s']:>9.2f} {r['steady_ms']:>10.2f}")
    # the planner's cost model must keep auto's steady state at least at
    # parity with the greedy path at small/medium T (the pre-planner auto was
    # 3-4x slower here); 25% + 0.2ms slack absorbs scheduler noise on sub-ms
    # timings
    for T in matched:
        auto_ms, pair_ms = steady[(T, "auto")], steady[(T, "pairwise")]
        assert auto_ms <= pair_ms * 1.25 + 0.2, (
            f"auto steady-state regressed vs pairwise at T={T}: "
            f"{auto_ms:.3f}ms vs {pair_ms:.3f}ms"
        )

    # the acceptance point: T=512 on the planned path (the parent races it
    # against the budgeted pairwise worker)
    scan512 = time_contract(BIG_T, BIG_K, "auto")
    results["chain"].append(scan512)
    print(f"{BIG_T:>5} {'auto':>9} {scan512['cold_s']:>9.2f} {scan512['steady_ms']:>10.2f}")
    # the compile-time war: cold trace+compile+run of the T=512 chain must
    # stay within half of the pre-planner 27.7s committed baseline (env
    # override for slow hosted runners)
    cold_budget = float(os.environ.get("REPRO_BENCH_COLD_BUDGET_S", "13.85"))
    assert scan512["cold_s"] <= cold_budget, (
        f"T={BIG_T} cold compile {scan512['cold_s']:.1f}s exceeds the "
        f"{cold_budget:.1f}s budget (REPRO_BENCH_COLD_BUDGET_S)"
    )

    # -- plan-cache level: same structure, fresh jit -> plan served from cache
    print("\n# plan-cache level: second same-structure contraction")
    warm_stats0 = plan_cache_stats()
    replan_T = matched[-1]
    t0 = time.perf_counter()
    r2 = time_contract(replan_T, BIG_K, "auto")
    warm_stats = plan_cache_stats()
    hits = warm_stats["hits"] - warm_stats0["hits"]
    misses = warm_stats["misses"] - warm_stats0["misses"]
    replan_ms = round((warm_stats["plan_time_s"] - warm_stats0["plan_time_s"]) * 1e3, 3)
    results["plan_cache"] = {
        "bench": "replan",
        "T": replan_T,
        "K": BIG_K,
        "hits": hits,
        "misses": misses,
        "replan_ms": replan_ms,
        "cold_s": r2["cold_s"],
        "stats": warm_stats,
    }
    print(f"  T={replan_T} refit: hits={hits} misses={misses} "
          f"plan_time={replan_ms}ms cold={r2['cold_s']}s "
          f"(total wall {time.perf_counter() - t0:.2f}s)")
    assert hits > 0 and misses == 0, (
        f"plan cache missed on a repeated structure (hits={hits}, "
        f"misses={misses}) — the structural fingerprint is unstable"
    )
    print(f"  plan cache: {warm_stats}")
    cc_stats = compilation_cache_stats()
    results["compilation_cache"] = cc_stats
    print(f"  compilation cache: {cc_stats}")

    print("\n# model level: TraceEnum_ELBO retrace counters (must stay 1)")
    # hmm_T sites -> hmm_T - 1 binary factors; both sizes stay above the
    # planner's ~18-edge scan crossover (smoke: 19 edges, full: 23), so the
    # model level genuinely exercises the fused chain lowering
    results["models"] = model_stage(
        hmm_T=20 if smoke else 24,
        hmm_K=4 if smoke else 8,
        gmm_N=512 if smoke else 4096,
        steps=8 if smoke else 25,
    )
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--json", default=str(REPO / "BENCH_enum.json"), help="output path")
    ap.add_argument("--budget", type=float, default=None,
                    help="wall-clock budget (s) for the pairwise T=512 attempt")
    ap.add_argument("--worker", nargs=3, metavar=("T", "K", "DISPATCH"),
                    help=argparse.SUPPRESS)  # internal: budgeted subprocess entry
    ap.add_argument("--stage", action="store_true",
                    help=argparse.SUPPRESS)  # internal: the planned-path worker
    args = ap.parse_args(argv)

    if args.worker:
        T, K, dispatch = int(args.worker[0]), int(args.worker[1]), args.worker[2]
        print(json.dumps(time_contract(T, K, dispatch, reps=5)))
        return 0
    if args.stage:
        print(json.dumps(planned_stage(args.smoke)))
        return 0

    results = _worker(["--stage"] + (["--smoke"] if args.smoke else []))
    scan512 = results["chain"][-1]

    # pairwise gets a budgeted worker of its own (its XLA compile alone
    # exceeds any sane budget). The budget scales with the machine: at least
    # 2x the measured hmm_scan wall time, so a slow CI runner can't fail the
    # comparison spuriously.
    budget = max(args.budget or (30.0 if args.smoke else 120.0), 2.0 * scan512["cold_s"])
    pair512 = time_contract_budgeted(BIG_T, BIG_K, "pairwise", budget_s=budget)
    results["chain"].append(pair512)
    if pair512.get("timed_out"):
        print(f"{BIG_T:>5} {'pairwise':>9} >{budget:>8.0f} {'(budget exceeded)':>10}")
        pairwise_total = budget
    elif pair512.get("failed"):
        raise RuntimeError(f"pairwise worker failed: {pair512['stderr']}")
    else:
        print(f"{BIG_T:>5} {'pairwise':>9} {pair512['cold_s']:>9.2f} {pair512['steady_ms']:>10.2f}")
        pairwise_total = pair512["cold_s"]
    scan_total = scan512["cold_s"]
    results["winner"] = {
        "T": BIG_T,
        "K": BIG_K,
        "planned_total_s": scan_total,
        "pairwise_total_s_lower_bound": pairwise_total,
        "speedup_lower_bound": round(pairwise_total / scan_total, 2),
    }
    assert scan_total < pairwise_total, (
        f"planned path ({scan_total:.1f}s) did not beat pairwise "
        f"({pairwise_total:.1f}s lower bound) at T={BIG_T}, K={BIG_K}"
    )
    print(f"planned path beats pairwise at T={BIG_T}, K={BIG_K}: "
          f">= {results['winner']['speedup_lower_bound']}x")

    Path(args.json).write_text(json.dumps(results, indent=2) + "\n")
    print(f"\nwrote {args.json}")
    print("OK: retrace counters == 1; planned dispatch wins the T=512 chain; "
          "plan cache hit on repeated structure")
    return 0


if __name__ == "__main__":
    sys.exit(main())
