"""Bring-up smoke test: drive every inference engine and its Pallas kernels
once on a TPU, through the public entry points, and check each result
against an independent reference.

    python chip_smoke.py             # one chip: the six phases below
    python chip_smoke.py --chips 4   # four chips: only the mesh= paths

One chip, in order (one process; nothing else may hold the chip):

  lm        smollm-135m at its published widths with use_pallas=True, built by
            `repro.launch.train.build`: 5 AdamW steps at batch 8 x seq 1024 on
            one `SyntheticTokens` batch; the loss must be finite and fall, and
            `flash_attention` / `categorical_logprob` must match the
            `reference` backend on that batch's shapes.
  mcmc      fused `MCMC(NUTS)` on the 64-school model (D=66), 1024 chains,
            200 warmup + 200 draws: one trace, split R-hat of mu < 1.05, and one
            `ops.leapfrog` call equal to the reference integrator.
  enum      `TraceEnum_ELBO` SVI steps on a T=24, K=32 HMM (each enumerated
            site takes an enum dim, so a model-level chain stays short) and a
            T=512, K=32 chain through `contract_log_factors`; the planner
            lowers both to `hmm_scan` and log Z equals a plain forward
            algorithm to rtol 1e-5. `semiring_matmul` at K=256 for both
            semirings matches float64 to rtol 1e-4 (TPU exp/log precision).
  gaussian  `gaussian_marginals` of a T=512 scalar Kalman model (lowered to
            `gaussian_scan`) against a float64 Rauch-Tung-Striebel smoother.
  smc       `SMC` at 65,536 particles: resampling ancestors bit-identical to
            the reference backend, log Z within 5 standard errors of the exact
            Kalman value.
  serve     `ServableModel.from_svi` behind `InferenceServer`: concurrent
            `:predict` HTTP requests through the `MicroBatcher`; every one is
            answered and compiles == buckets touched.

Four chips (``--chips 4``): MCMC chains, SVI particles and SMC particles on a
4-device mesh, each against the same run with no mesh, and each asserting
that its sharded arrays span all 4 devices.

Each phase prints one JSON line (seconds, compile seconds, persistent-cache
hits, its check values, the kernel backend it resolved). Any failed check or
exception exits non-zero. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script refuses to run anywhere but on a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from dataclasses import dataclass
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))


@dataclass(frozen=True)
class Sizes:
    lm_full: bool = True          # published widths (else the SMOKE config)
    lm_batch: int = 8
    lm_seq: int = 1024
    lm_steps: int = 5
    schools: int = 64
    chains: int = 1024
    warmup: int = 200
    draws: int = 200
    hmm_model_T: int = 24
    hmm_T: int = 512
    hmm_K: int = 32
    semiring_K: int = 256
    enum_steps: int = 3
    kalman_T: int = 512
    particles: int = 65536
    smc_T: int = 64
    smc_runs: int = 8
    serve_rows: tuple = (1, 2, 3, 4, 5, 8, 8, 3)
    mesh_chains: int = 64
    mesh_particles: int = 8192


FULL = Sizes()


# ---------------------------------------------------------------------------
# shared models and references
# ---------------------------------------------------------------------------


def _max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def _logsumexp(x: np.ndarray, axis: int) -> np.ndarray:
    m = np.max(x, axis=axis, keepdims=True)
    return np.squeeze(m, axis) + np.log(np.sum(np.exp(x - m), axis=axis))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _schools(J: int, seed: int = 0):
    """The non-centered school model of `benchmarks/mcmc_bench.py` (D=J+2)."""
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro.core import primitives as P

    gen = np.random.default_rng(seed)
    y = jnp.asarray(gen.normal(5.0, 8.0, J).astype(np.float32))
    sigma = jnp.asarray(gen.uniform(8.0, 18.0, J).astype(np.float32))

    def model(y, sigma):
        mu = P.sample("mu", dist.Normal(0.0, 5.0))
        log_tau = P.sample("log_tau", dist.Normal(0.0, 1.0))
        with P.plate("J", y.shape[0]):
            theta = P.sample("theta", dist.Normal(0.0, 1.0))
            P.sample("obs", dist.Normal(mu + jnp.exp(log_tau) * theta, sigma), obs=y)

    return model, (y, sigma)


# scalar linear-Gaussian state-space model shared by the gaussian/smc phases
A, S_TRANS, S_OBS, P0 = 0.9, 0.3, 0.5, 1.0


def _kalman_observations(T: int, seed: int = 0) -> np.ndarray:
    gen = np.random.default_rng(seed)
    xs = [gen.normal(0.0, P0)]
    for _ in range(T - 1):
        xs.append(A * xs[-1] + gen.normal(0.0, S_TRANS))
    return np.asarray([x + gen.normal(0.0, S_OBS) for x in xs], np.float32)


def _kalman_reference(ys: np.ndarray):
    """Float64 Kalman filter + RTS smoother: smoother means/variances and the
    exact log marginal likelihood."""
    ys = np.asarray(ys, np.float64)
    T = len(ys)
    fm, fp, pm_, pp_ = np.zeros(T), np.zeros(T), np.zeros(T), np.zeros(T)
    logz = 0.0
    for t in range(T):
        pm, pp = (0.0, P0 * P0) if t == 0 else (A * fm[t - 1], A * A * fp[t - 1] + S_TRANS**2)
        pm_[t], pp_[t] = pm, pp
        s = pp + S_OBS**2
        logz += -0.5 * ((ys[t] - pm) ** 2 / s + np.log(2 * np.pi * s))
        k = pp / s
        fm[t], fp[t] = pm + k * (ys[t] - pm), (1 - k) * pp
    sm, sp = fm.copy(), fp.copy()
    for t in range(T - 2, -1, -1):
        g = fp[t] * A / pp_[t + 1]
        sm[t] = fm[t] + g * (sm[t + 1] - pm_[t + 1])
        sp[t] = fp[t] + g * g * (sp[t + 1] - pp_[t + 1])
    return sm, sp, logz


def _ssm_programs():
    from repro import distributions as dist
    from repro.core import primitives as P

    def model_init(y):
        x = P.sample("x", dist.Normal(0.0, P0))
        P.sample("y", dist.Normal(x, S_OBS), obs=y)
        return {"x": x}

    def model_step(carry, y):
        x = P.sample("x", dist.Normal(A * carry["x"], S_TRANS))
        P.sample("y", dist.Normal(x, S_OBS), obs=y)
        return {"x": x}

    return model_init, model_step


def _lowered_kernels(jitted, *args) -> int:
    """How many Pallas TPU kernels the lowered program calls."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_lm(sz: Sizes, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.data import DataConfig, SyntheticTokens
    from repro.kernels import ops
    from repro.launch.train import build
    from repro.models import init_params

    arch = "smollm-135m"
    base = configs.get_config(arch) if sz.lm_full else configs.get_smoke_config(arch)
    cfg = base.replace(use_pallas=True)
    optimizer, step_fn = build(cfg, lr=1e-4, steps=sz.lm_steps)
    opt_state = optimizer.init(init_params(cfg, jax.random.PRNGKey(seed)))
    data = SyntheticTokens(DataConfig(cfg.vocab, sz.lm_seq, sz.lm_batch, seed=seed))
    batch = data.global_batch(0)
    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    losses = []
    for _ in range(sz.lm_steps):
        opt_state, metrics = jit_step(opt_state, batch)
        losses.append(float(metrics["loss"]))
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    # the two kernels at this batch's shapes, against the reference backend
    B, S, H, K = sz.lm_batch, sz.lm_seq, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.d_model // H
    dt = jnp.dtype(cfg.compute_dtype)
    kq, kk, kv, kl = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    q = jax.random.normal(kq, (B, H, S, hd), dt)
    k = jax.random.normal(kk, (B, K, S, hd), dt)
    v = jax.random.normal(kv, (B, K, S, hd), dt)
    attn = ops.flash_attention(q, k, v)
    attn_ref = ops.flash_attention(q, k, v, backend="reference")
    attn_err = _max_err(attn, attn_ref)
    # both round a bf16 output (8 mantissa bits): allow two units in the last place
    _check(np.allclose(np.asarray(attn, np.float32), np.asarray(attn_ref, np.float32),
                       rtol=2**-7, atol=2**-7),
           f"flash_attention vs reference: max err {attn_err}")

    logits = 4.0 * jax.random.normal(kl, (B, S, cfg.vocab), dt)
    lp = ops.categorical_logprob(logits, batch["targets"])
    lp_ref = ops.categorical_logprob(logits, batch["targets"], backend="reference")
    lp_err = _max_err(lp, lp_ref)
    _check(lp_err <= 1e-4 * max(1.0, float(jnp.max(jnp.abs(lp_ref)))),
           f"categorical_logprob vs reference: max err {lp_err}")
    return {"losses": losses, "flash_max_err": attn_err, "logprob_max_err": lp_err}


def _flat_potential(model, args):
    import jax
    from jax.flatten_util import ravel_pytree

    from repro.infer import initialize_model

    pe, _, inits = initialize_model(jax.random.PRNGKey(0), model, args)
    flat, unravel = ravel_pytree(inits)
    return (lambda zvec: pe(unravel(zvec))), flat.shape[0]


def phase_mcmc(sz: Sizes, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.infer import MCMC, NUTS, split_rhat
    from repro.kernels import ops

    model, args = _schools(sz.schools, seed)
    mcmc = MCMC(NUTS(model), num_warmup=sz.warmup, num_samples=sz.draws,
                num_chains=sz.chains)
    mcmc.run(jax.random.PRNGKey(seed), *args)
    mu = mcmc.get_samples(group_by_chain=True)["mu"]
    rhat = float(split_rhat(mu))
    _check(mcmc.num_traces == 1, f"MCMC traced {mcmc.num_traces} times")
    _check(rhat < 1.05, f"split R-hat of mu = {rhat}")

    pe_flat, D = _flat_potential(model, args)
    C = sz.chains
    kz, kr, kn = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    z = 0.5 * jax.random.normal(kz, (C, D))
    r = jax.random.normal(kr, (C, D))
    inv_mass = jnp.ones((C, D))
    eps = jnp.full((C,), 0.05)
    n = jax.random.randint(kn, (C,), 0, 11)

    def run(backend):
        f = jax.jit(lambda *a: ops.leapfrog(*a, pe_flat, max_steps=16, backend=backend))
        return f(z, r, inv_mass, eps, n)

    got, want = run(None), run("reference")
    errs = [_max_err(a, b) for a, b in zip(got, want)]
    for a, b, name in zip(got, want, ("z", "r", "potential")):
        _check(np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4),
               f"leapfrog {name} vs reference: max err {_max_err(a, b)}")
    return {"num_traces": mcmc.num_traces, "rhat_mu": rhat,
            "mean_mu": float(jnp.mean(mu)), "leapfrog_max_err": max(errs)}


def _hmm(T: int, K: int, seed: int = 0):
    """An enumerated HMM with Gaussian emissions and one learnable scale."""
    import jax
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro.core import primitives as P
    from repro.infer import config

    key = jax.random.PRNGKey(seed)
    trans = jax.random.dirichlet(key, jnp.ones(K), (K,))
    init = jnp.ones(K) / K
    locs = jnp.linspace(-2.0, 2.0, K)
    obs = jax.random.normal(jax.random.fold_in(key, 1), (T,))

    @config(enumerate=True)
    def model(obs):
        scale = P.param("scale", jnp.asarray(1.0))
        z = P.sample("z_0", dist.Categorical(init))
        P.sample("x_0", dist.Normal(locs[z], scale), obs=obs[0])
        for t in range(1, obs.shape[0]):
            z = P.sample(f"z_{t}", dist.Categorical(trans[z]))
            P.sample(f"x_{t}", dist.Normal(locs[z], scale), obs=obs[t])

    def forward_logz(scale):
        """Plain float64 forward algorithm on the host: the reference log Z."""
        o, lt = np.asarray(obs, np.float64), np.log(np.asarray(trans, np.float64))
        mu = np.asarray(locs, np.float64)
        emit = (-0.5 * ((o[:, None] - mu[None, :]) / scale) ** 2
                - np.log(scale * math.sqrt(2 * math.pi)))
        alpha = -np.log(K) + emit[0]
        for t in range(1, len(o)):
            alpha = _logsumexp(alpha[:, None] + lt, axis=0) + emit[t]
        return float(_logsumexp(alpha, axis=0))

    return model, obs, forward_logz


def _chain(T: int, K: int, seed: int = 0):
    """A T-step chain of random K x K log-factors, a function that lays them
    out the way the enumeration engine hands them to the planner (z_t on
    enum dim -(t+1)), and the chain's log Z by a plain forward algorithm."""
    import jax

    key = jax.random.PRNGKey(seed)
    trans = jax.random.normal(key, (T, K, K))
    obs = jax.random.normal(jax.random.fold_in(key, 1), (T, K))
    prior = jax.random.normal(jax.random.fold_in(key, 2), (K,))

    def factors(trans, obs, prior):
        fs = [(frozenset(), prior, None)]
        for t in range(1, T + 1):
            fs.append((frozenset(), trans[t - 1].reshape((K, K) + (1,) * (t - 1)), None))
            fs.append((frozenset(), obs[t - 1].reshape((K,) + (1,) * t), None))
        return fs, frozenset(-(t + 1) for t in range(T + 1))

    # float64 forward algorithm on the host; trans[t][j, i] links z_t = j
    # to z_{t-1} = i
    tr, ob = np.asarray(trans, np.float64), np.asarray(obs, np.float64)
    alpha = np.asarray(prior, np.float64)
    for t in range(T):
        alpha = _logsumexp(alpha[None, :] + tr[t], axis=1) + ob[t]
    return (trans, obs, prior), factors, float(_logsumexp(alpha, axis=0))


def phase_enum(sz: Sizes, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import optim
    from repro.infer import SVI, TraceEnum_ELBO
    from repro.infer.contract import contract_log_factors
    from repro.kernels import ops

    on_tpu = ops.resolve_backend() == "tpu"
    # model level: each sequential enumerated site takes its own enum dim,
    # and arrays cap the rank, so a model-level chain stays this short
    model, obs, forward_logz = _hmm(sz.hmm_model_T, sz.hmm_K, seed)
    elbo = TraceEnum_ELBO()
    svi = SVI(model, lambda obs: None, optim.Adam(0.01), elbo)
    state = svi.init(jax.random.PRNGKey(seed), obs)
    scale0 = float(svi.get_params(state)["scale"])
    kernels = _lowered_kernels(svi.update_jit, state, obs)
    _check(kernels > 0 or not on_tpu, "the enumerated HMM step calls no Pallas kernel")
    losses = []
    for i in range(sz.enum_steps):
        state, loss = svi.update_jit(state, obs + 1e-4 * i)
        losses.append(float(loss))
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    logz, logz_ref = -losses[0], forward_logz(scale0)
    _check(abs(logz - logz_ref) <= 1e-5 * abs(logz_ref),
           f"HMM log Z {logz} vs forward algorithm {logz_ref}")

    # the long chain, through the planner entry every enumeration engine calls
    inputs, factors, chain_ref = _chain(sz.hmm_T, sz.hmm_K, seed)

    def chain_logz(*inputs):
        fs, pool = factors(*inputs)
        return jnp.squeeze(contract_log_factors(fs, {}, pool))

    run = jax.jit(chain_logz)
    chain_kernels = _lowered_kernels(run, *inputs)
    _check(chain_kernels > 0 or not on_tpu, "the T-step chain calls no Pallas kernel")
    chain = float(run(*inputs))
    _check(abs(chain - chain_ref) <= 1e-5 * abs(chain_ref),
           f"chain log Z {chain} vs forward algorithm {chain_ref}")

    # the K-tiled kernel against float64 on the host; the XLA reference
    # backend's own error on the chip is reported beside it. TPU f32
    # exp/log are accurate to ~5e-6 relative, not to the last ulp.
    K = sz.semiring_K
    a = jax.random.normal(jax.random.PRNGKey(seed + 2), (K, K))
    b = jax.random.normal(jax.random.PRNGKey(seed + 3), (K, K))
    x = np.asarray(a, np.float64)[:, :, None] + np.asarray(b, np.float64)[None, :, :]
    exact = {"logsumexp": _logsumexp(x, axis=1), "max": np.max(x, axis=1)}
    semiring_err = {}
    for semiring, truth in exact.items():
        got = ops.semiring_matmul(a, b, semiring=semiring)
        want = ops.semiring_matmul(a, b, semiring=semiring, backend="reference")
        err, ref_err = _max_err(got, truth), _max_err(want, truth)
        _check(np.allclose(np.asarray(got, np.float64), truth, rtol=1e-4, atol=1e-6),
               f"semiring_matmul[{semiring}] K={K}: max err {err} (reference {ref_err})")
        semiring_err[semiring] = {"kernel": err, "reference": ref_err}
    return {"model_logz": logz, "model_logz_ref": logz_ref, "losses": losses,
            "model_kernels": kernels, "chain_logz": chain, "chain_logz_ref": chain_ref,
            "chain_kernels": chain_kernels, "semiring_max_err": semiring_err}


def phase_gaussian(sz: Sizes, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro.core import primitives as P
    from repro.infer import gaussian_marginals
    from repro.kernels import ops

    T = sz.kalman_T
    ys = jnp.asarray(_kalman_observations(T, seed))
    probe = sorted({0, 1, T // 2, T - 1})
    sites = [f"x{t}" for t in probe]
    gm = {"marginalize": "gaussian"}

    def marginals(ys):
        def model():
            x = P.sample("x0", dist.Normal(0.0, P0), infer=gm)
            P.sample("y0", dist.Normal(x, S_OBS), obs=ys[0])
            for t in range(1, T):
                x = P.sample(f"x{t}", dist.Normal(A * x, S_TRANS), infer=gm)
                P.sample(f"y{t}", dist.Normal(x, S_OBS), obs=ys[t])

        return gaussian_marginals(model, jax.random.PRNGKey(seed), sites=sites)

    run = jax.jit(marginals)
    kernels = _lowered_kernels(run, ys)
    _check(kernels > 0 or ops.resolve_backend() != "tpu",
           "gaussian_marginals calls no Pallas kernel")
    out = run(ys)
    sm, sp, _ = _kalman_reference(np.asarray(ys))
    errs = []
    for t, site in zip(probe, sites):
        m, v = (float(x) for x in out[site])
        errs.append(max(abs(m - sm[t]), abs(v - sp[t])))
        _check(np.allclose([m, v], [sm[t], sp[t]], rtol=1e-4, atol=1e-5),
               f"{site}: ({m}, {v}) vs smoother ({sm[t]}, {sp[t]})")
    return {"probe": probe, "max_err": max(errs), "kernels": kernels}


def phase_smc(sz: Sizes, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.infer import SMC
    from repro.kernels import ops

    ys = jnp.asarray(_kalman_observations(sz.smc_T, seed))
    _, _, logz_exact = _kalman_reference(np.asarray(ys))
    smc = SMC(*_ssm_programs(), num_particles=sz.particles)
    logzs = []
    for rep in range(sz.smc_runs):
        smc.run(jax.random.PRNGKey(seed + rep), ys)
        logzs.append(float(smc.log_evidence()))
    _check(smc.num_traces == 1, f"SMC traced {smc.num_traces} times")
    mean, se = float(np.mean(logzs)), float(np.std(logzs, ddof=1) / math.sqrt(len(logzs)))
    _check(abs(mean - logz_exact) <= 5.0 * se,
           f"log Z {mean} +- {se} vs exact Kalman {logz_exact}")

    lw = 2.0 * jax.random.normal(jax.random.PRNGKey(seed + 100), (sz.particles,))
    anc = ops.resample(lw, 0.37)
    anc_ref = ops.resample(lw, 0.37, backend="reference")
    _check(bool(jnp.array_equal(anc, anc_ref)), "resample ancestors differ from reference")
    return {"logz_mean": mean, "logz_se": se, "logz_exact": logz_exact,
            "num_traces": smc.num_traces, "ancestors_equal": True,
            "distinct_ancestors": int(np.unique(np.asarray(anc)).size)}


def phase_serve(sz: Sizes, seed: int = 0) -> dict:
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro import optim
    from repro.core import primitives as P
    from repro.infer import SVI, AutoNormal, Trace_ELBO
    from repro.serve import InferenceServer, ServableModel

    def model(x, y=None):
        w = P.sample("w", dist.Normal(jnp.zeros(2), 1.0).to_event(1))
        with P.plate("B", x.shape[0]):
            mu = P.deterministic("mu", x @ w)
            P.sample("y", dist.Normal(mu, 0.1), obs=y)

    x = jax.random.normal(jax.random.PRNGKey(seed), (64, 2))
    y = x @ jnp.asarray([1.0, -2.0])
    guide = AutoNormal(model)
    svi = SVI(model, guide, optim.Adam(0.05), Trace_ELBO())
    state, _ = svi.run(jax.random.PRNGKey(seed + 1), 50, x, y=y)
    params = svi.optim.get_params(state.optim_state)
    num_samples = 4
    sm = ServableModel.from_svi("reg", model, guide, params,
                                num_samples=num_samples, max_batch=8)

    gen = np.random.default_rng(seed)
    with InferenceServer({"reg": sm}, max_wait_ms=5.0) as server:
        def post(rows):
            body = json.dumps({"inputs": gen.normal(size=(rows, 2)).tolist()}).encode()
            req = urllib.request.Request(
                server.address + "/v1/models/reg:predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as r:
                return r.status, json.loads(r.read())

        with ThreadPoolExecutor(max_workers=len(sz.serve_rows)) as pool:
            replies = list(pool.map(post, sz.serve_rows))
    for rows, (status, payload) in zip(sz.serve_rows, replies):
        _check(status == 200, f"request of {rows} rows answered {status}")
        shape = np.asarray(payload["outputs"]["mu"]).shape
        _check(shape == (num_samples, rows), f"{rows}-row request got mu of shape {shape}")
    buckets = sorted(sm.buckets_touched)
    _check(sm.num_traces == len(buckets),
           f"{sm.num_traces} compiles for buckets {buckets}")
    return {"requests": len(replies), "compiles": sm.num_traces, "buckets": buckets}


PHASES = (("lm", phase_lm), ("mcmc", phase_mcmc), ("enum", phase_enum),
          ("gaussian", phase_gaussian), ("smc", phase_smc), ("serve", phase_serve))


# ---------------------------------------------------------------------------
# four-chip phase: every mesh= path against its unsharded run
# ---------------------------------------------------------------------------


def _devices_spanned(x) -> int:
    return len(x.sharding.device_set)


def phase_mesh(sz: Sizes, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro import optim
    from repro.core import primitives as P
    from repro.distributed import make_mesh
    from repro.infer import (
        MCMC, NUTS, SMC, SVI, AutoNormal, Trace_ELBO, effective_sample_size, split_rhat,
    )

    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",))
    out = {"devices": n_dev}

    # MCMC chains: mesh="auto" against mesh=None on the same keys
    model, args = _schools(sz.schools, seed)
    runs = {}
    for name, m in (("local", None), ("sharded", "auto")):
        mcmc = MCMC(NUTS(model), num_warmup=sz.warmup, num_samples=sz.draws,
                    num_chains=sz.mesh_chains, mesh=m)
        mcmc.run(jax.random.PRNGKey(seed), *args)
        runs[name] = mcmc.get_samples(group_by_chain=True)["mu"]
    mu_l, mu_s = runs["local"], runs["sharded"]
    spanned = _devices_spanned(mu_s)
    _check(spanned == n_dev, f"MCMC chains span {spanned} of {n_dev} devices")
    rhat = float(split_rhat(mu_s))
    _check(rhat < 1.05, f"sharded MCMC split R-hat of mu = {rhat}")
    # cross-chain adaptation reduces over the sharded axis, so the two runs
    # may round differently and then drift apart; their posteriors must agree
    se = math.sqrt(sum(float(jnp.var(x)) / float(effective_sample_size(x))
                       for x in (mu_l, mu_s)))
    diff = abs(float(jnp.mean(mu_l)) - float(jnp.mean(mu_s)))
    _check(diff <= 5.0 * se, f"posterior mean of mu differs by {diff} (se {se})")
    out["mcmc"] = {"spanned": spanned, "rhat_mu": rhat, "mean_diff": diff,
                   "bit_identical": bool(jnp.array_equal(mu_l, mu_s))}

    # SVI particles: Trace_ELBO(num_particles=...) sharded against unsharded
    def reg(data):
        loc = P.sample("loc", dist.Normal(0.0, 10.0))
        with P.plate("N", data.shape[0]):
            P.sample("obs", dist.Normal(loc, 1.0), obs=data)

    data = 1.5 + 0.7 * jax.random.normal(jax.random.PRNGKey(seed), (1024,))
    traj = {}
    for name, m in (("local", None), ("sharded", mesh)):
        svi = SVI(reg, AutoNormal(reg), optim.Adam(0.05),
                  Trace_ELBO(num_particles=4 * n_dev), mesh=m)
        state = svi.init(jax.random.PRNGKey(seed + 1), data)
        losses = []
        for _ in range(20):
            state, loss = svi.update_jit(state, data)
            losses.append(float(loss))
        traj[name] = np.asarray(losses)
        if m is not None:
            hlo = svi.update_jit.lower(state, data).compile().as_text()
            all_reduces = hlo.count("all-reduce")
    _check(all_reduces > 0 or n_dev == 1, "sharded SVI step has no cross-device reduction")
    _check(np.allclose(traj["local"], traj["sharded"], rtol=1e-4),
           f"SVI losses differ: {traj['local'][-1]} vs {traj['sharded'][-1]}")
    out["svi"] = {"all_reduces": all_reduces,
                  "loss_max_rel_diff": float(np.max(np.abs(traj["local"] - traj["sharded"])
                                                    / np.abs(traj["local"])))}

    # SMC particles: mesh= against unsharded
    ys = jnp.asarray(_kalman_observations(sz.smc_T, seed))
    res = {}
    for name, m in (("local", None), ("sharded", mesh)):
        smc = SMC(*_ssm_programs(), num_particles=sz.mesh_particles, mesh=m)
        smc.run(jax.random.PRNGKey(seed), ys)
        res[name] = (float(smc.log_evidence()), smc.log_weights)
    spanned = _devices_spanned(res["sharded"][1])
    _check(spanned == n_dev, f"SMC particles span {spanned} of {n_dev} devices")
    _, _, logz_exact = _kalman_reference(np.asarray(ys))
    tol = 10.0 * sz.smc_T / math.sqrt(sz.mesh_particles)
    for name, (logz, _) in res.items():
        _check(abs(logz - logz_exact) < tol, f"{name} SMC log Z {logz} vs exact {logz_exact}")
    out["smc"] = {"spanned": spanned, "logz_local": res["local"][0],
                  "logz_sharded": res["sharded"][0], "logz_exact": logz_exact,
                  "bit_identical": bool(jnp.array_equal(res["local"][1], res["sharded"][1]))}
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def run_phase(name, fn, sz: Sizes) -> None:
    """Run one phase inside the telemetry span ``chip_smoke.<name>`` and print
    its line: seconds, backend compile seconds and persistent-cache hits (a
    hit replaces a compile with a read), from the span's JAX events."""
    from repro import telemetry
    from repro.kernels import ops

    with telemetry.span(f"chip_smoke.{name}") as record:
        check = fn(sz)
    events = record["jax_events"]
    line = {"phase": name, "seconds": round(record["end"] - record["start"], 3),
            "compile_s": round(events.get(BACKEND_COMPILE, 0.0), 3),
            "cache_hits": events.get(telemetry.CACHE_HIT, 0),
            "backend": ops.resolve_backend(), "check": check}
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh= paths on a four-chip host")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script ({e})",
              file=sys.stderr)
        return 2
    cache = enable_compilation_cache()

    import jax

    from repro.kernels import ops

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 1
    resolved = {op: ops.resolve_backend() for op in ops._SUPPORT}
    if any(b != "tpu" or "tpu" not in ops._SUPPORT[op] for op, b in resolved.items()):
        print(f"chip_smoke: kernel backends do not resolve to tpu: {resolved}",
              file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache": str(cache), "jax": jax.__version__}), flush=True)

    phases = (("mesh", phase_mesh),) if args.chips == 4 else PHASES
    for name, fn in phases:
        run_phase(name, fn, FULL)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
