"""Stochastic volatility of daily S&P 500 returns (Inference Gym
``StochasticVolatilitySP500``), filtered at fixed parameters, for the SMC
driver.

The model is the source's, centered, with its three parameters held at the
values in ``sv_sp500.json`` under ``assumed`` (a particle filter runs at
fixed parameters inside PMMH or SMC^2):

    h_1 ~ N(mu, sigma / sqrt(1 - phi^2)),
    h_t ~ N(mu + phi (h_{t-1} - mu), sigma),       t = 2..T
    y_t ~ N(0, exp(h_t / 2)),                       t = 1..T

with T = 2516 as published. The published returns are not in the
repository, so `make_data` simulates T returns from the model once, from
the configuration's ``data_seed``: every run filters the same series, and
only the filter's keys follow the run's seed.

Beside the program's model this file holds the plain float64 reference the
benchmark compares the timed path with, and the work counts:

* `log_obs_ref`: the observation log-density log N(y; 0, exp(h / 2)) at any
  h, in numpy at any dtype (float64 for the reference, bfloat16 for the
  control);
* `grid_filter_ref`: a point-mass filter over h on K grid points spanning
  the stationary law by ``grid_half_width_sd`` standard deviations either
  side: log Z = log p(y_1..T) and the filtering mean and variance of h_t
  for every t, exact up to its grid;
* `flops_per_particle_step`, `resample_bytes_per_call`: counted by hand,
  functions of the shapes alone.
"""
from __future__ import annotations

import math

import numpy as np

LOG_SQRT_2PI = 0.5 * math.log(2.0 * math.pi)


def params(spec: dict):
    a = spec["assumed"]
    return float(a["mu"]), float(a["phi"]), float(a["sigma"])


def stationary_sd(spec: dict) -> float:
    _, phi, sigma = params(spec)
    return sigma / math.sqrt(1.0 - phi * phi)


def make_data(spec: dict, seed: int) -> dict:
    """T returns simulated from the model at its fixed parameters, from the
    configuration's ``data_seed``: the same for every run seed. float32, as
    the program filters them; the latent path is kept for the tests."""
    del seed
    mu, phi, sigma = params(spec)
    gen = np.random.default_rng(int(spec["assumed"]["data_seed"]))
    T = int(spec["T"])
    e = gen.standard_normal(T)
    h = np.empty(T)
    h[0] = mu + stationary_sd(spec) * e[0]
    for t in range(1, T):
        h[t] = mu + phi * (h[t - 1] - mu) + sigma * e[t]
    y = np.exp(h / 2.0) * gen.standard_normal(T)
    return {"y": y.astype(np.float32), "h": h}


def program(spec: dict):
    """(model_init, model_step) as the program runs them: the carry is h."""
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro.core import primitives as P

    mu, phi, sigma = params(spec)
    sd0 = stationary_sd(spec)
    site = spec["site"]

    def model_init(y):
        h = P.sample(site, dist.Normal(mu, sd0))
        P.sample("y", dist.Normal(0.0, jnp.exp(h / 2.0)), obs=y)
        return h

    def model_step(h_prev, y):
        h = P.sample(site, dist.Normal(mu + phi * (h_prev - mu), sigma))
        P.sample("y", dist.Normal(0.0, jnp.exp(h / 2.0)), obs=y)
        return h

    return model_init, model_step


def log_obs_ref(h, y, dtype=np.float64) -> np.ndarray:
    """log N(y; 0, exp(h / 2)) elementwise (h and y broadcast), every
    operation in `dtype`, with the program's normalising constant."""
    dt = np.dtype(dtype).type
    h = np.asarray(h, dt)
    y = np.asarray(y, dt)
    log_scale = (h / dt(2.0)).astype(dt)
    z = (y / np.exp(log_scale)).astype(dt)
    return (dt(-0.5) * z * z - log_scale - dt(LOG_SQRT_2PI)).astype(dt)


def grid(spec: dict, K: int) -> np.ndarray:
    """K equally spaced points over mu +- half width x the stationary sd."""
    mu, _, _ = params(spec)
    w = float(spec["grid_half_width_sd"]) * stationary_sd(spec)
    return np.linspace(mu - w, mu + w, K)


def grid_filter_ref(spec: dict, data: dict, K: int, dtype=np.float64,
                    log_obs=log_obs_ref) -> dict:
    """The point-mass filter over h on `grid(spec, K)`: the prior and every
    transition as masses on the grid (each column of the transition matrix
    normalised to 1), the observation density `log_obs(h, y_t, dtype)` at
    each point, the running normaliser. Returns {"log_z": log p(y_1..T),
    "mean": E[h_t | y_1..t], "var": Var[h_t | y_1..t]} (shapes (), (T,),
    (T,)).

    In `dtype`: every array is held in it and every elementwise operation
    rounds to it; sums and the matrix-vector products accumulate in float64
    for float64 and in float32 otherwise (as a bfloat16 matrix product on
    the chip does), their results rounded to `dtype`."""
    dt = np.dtype(dtype).type
    acc = np.float64 if np.dtype(dtype) == np.float64 else np.float32
    mu, phi, sigma = params(spec)
    x = grid(spec, K)

    def rnd(a):
        return np.asarray(a, dt)

    def logsum(a):
        a = np.asarray(a, acc)
        m = a.max()
        return m + np.log(np.sum(np.exp(a - m), dtype=acc))

    z = (x[:, None] - (mu + phi * (x[None, :] - mu))) / sigma
    trans = np.exp(-0.5 * z * z)
    trans = rnd(trans / trans.sum(axis=0, keepdims=True)).astype(acc)
    prior = np.exp(-0.5 * ((x - mu) / stationary_sd(spec)) ** 2)
    pred = rnd(prior / prior.sum())
    xs = rnd(x)
    y = np.asarray(data["y"], np.float64)
    T = y.shape[0]
    log_z = dt(0.0)
    mean, var = np.empty(T), np.empty(T)
    for t in range(T):
        if t:
            pred = rnd(trans @ filt.astype(acc))
        # the mass of each point times its observation density, in logs
        lp = rnd(np.log(np.maximum(pred.astype(acc), np.finfo(acc).tiny)).astype(dt)
                 + log_obs(xs, y[t], dtype))
        step = logsum(lp)
        log_z = rnd(log_z + rnd(step))
        filt = rnd(np.exp(np.asarray(lp, acc) - step))
        m = np.sum(filt.astype(acc) * xs.astype(acc), dtype=acc)
        mean[t] = float(rnd(m))
        var[t] = float(rnd(np.sum(filt.astype(acc) * (xs.astype(acc) - m) ** 2, dtype=acc)))
    return {"log_z": float(log_z), "mean": mean, "var": var}


def flops_per_particle_step(spec: dict) -> int:
    """Floating-point operations the filter needs for one particle at one
    step, counting exp and log as one operation each, precomputing only
    what does not depend on the particle (mu, phi, sigma, log sqrt(2 pi)),
    and leaving out the random draws and the search that picks ancestors:

    transition, 5 (h - mu, x phi, + mu, sigma x noise, +);
    observation log-density, 7 (h / 2 as the log scale, exp, y / scale,
    square, x -0.5, - the log scale, - log sqrt(2 pi));
    weight update, 1 (+ the incremental log-weight);
    logsumexp of the weights, 4 (max, -, exp, +);
    normalised weights and ESS, 4 (- the normaliser, exp, square, +);
    cumulative sum of the normalised weights for resampling, 1;
    the filtering mean, 2 (weight x h, +).
    So 24."""
    del spec
    return 5 + 7 + 1 + 4 + 4 + 1 + 2


def resample_bytes_per_call(num_particles: int) -> int:
    """Bytes one resampling of N particles moves, whatever implements it:
    N float32 weights in, N int32 ancestor indices out."""
    return 8 * num_particles
