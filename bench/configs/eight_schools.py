"""Eight schools, non-centered (Rubin 1981; posteriordb
``eight_schools_noncentered``), for the NUTS driver.

The model and data are the source's, as published:

    mu ~ N(0, 5),  tau ~ half-Cauchy(0, 5),  theta_trans_j ~ N(0, 1),
    y_j ~ N(mu + tau * theta_trans_j, sigma_j),   j = 1..8

with the eight published (y_j, sigma_j) held in ``eight_schools.json``. The
program samples tau on its positive support, so NUTS moves in the
unconstrained position (mu, log_tau, theta_trans), D = J + 2 = 10, and its
potential carries the Jacobian log_tau. The data do not depend on the seed:
every run samples the same posterior and only the sampler's keys differ.

Beside the program's model this file holds the plain float64 reference the
benchmark compares the timed path with, and the work one gradient needs:

* `unconstrained` / `constrained`: the program's draws (mu, tau,
  theta_trans) to the unconstrained position and back;
* `potential_ref`: the potential energy -log p(mu, tau, theta_trans, y) -
  log_tau at each unconstrained position, in numpy at any dtype (float64
  for the reference, bfloat16 for the control);
* `moments_ref`: the posterior mean and variance of `mu` and `log_tau` by
  one-dimensional quadrature over log_tau. Given tau the schools integrate
  out in closed form (y_j ~ N(mu, tau^2 + sigma_j^2)), and so does mu (a
  Gaussian in mu), so the reference needs no sampler;
* `flops_per_grad`, `leapfrog_bytes_per_step`: counted from the shapes.
"""
from __future__ import annotations

import math

import numpy as np

# the scalar sites of the unconstrained position whose posterior moments the
# check compares and whose ESS the metric takes
CHECK_SITES = ("mu", "log_tau")
MU_SCALE = 5.0
TAU_SCALE = 5.0


def make_data(spec: dict, seed: int) -> dict:
    """The published data; the same for every seed."""
    return {"y": np.asarray(spec["data"]["y"], np.float32),
            "sigma": np.asarray(spec["data"]["sigma"], np.float32)}


def program(spec: dict, data: dict):
    """The model as the program runs it, and its arguments."""
    import jax.numpy as jnp

    from repro import distributions as dist
    from repro.core import primitives as P

    def model(y, sigma):
        mu = P.sample("mu", dist.Normal(0.0, MU_SCALE))
        tau = P.sample("tau", dist.HalfCauchy(TAU_SCALE))
        with P.plate("J", y.shape[0]):
            theta_trans = P.sample("theta_trans", dist.Normal(0.0, 1.0))
            P.sample("obs", dist.Normal(mu + tau * theta_trans, sigma), obs=y)

    return model, (jnp.asarray(data["y"]), jnp.asarray(data["sigma"]))


def unconstrained(draws: dict) -> dict:
    """The program's draws {mu, tau, theta_trans} as the position NUTS
    moves in, in float64."""
    return {"mu": np.asarray(draws["mu"], np.float64),
            "log_tau": np.log(np.asarray(draws["tau"], np.float64)),
            "theta_trans": np.asarray(draws["theta_trans"], np.float64)}


def constrained(position: dict) -> dict:
    """The inverse of `unconstrained`, in float32 as the program reports."""
    return {"mu": np.asarray(position["mu"], np.float32),
            "tau": np.exp(np.asarray(position["log_tau"], np.float64)).astype(np.float32),
            "theta_trans": np.asarray(position["theta_trans"], np.float32)}


def _log_normal(x, loc, scale, dt):
    z = (x - loc) / scale
    return (dt(-0.5) * z * z - np.log(scale) - dt(0.5 * math.log(2 * math.pi))).astype(dt)


def potential_ref(position: dict, data: dict, dtype=np.float64) -> np.ndarray:
    """-log p(mu, tau, theta_trans, y) - log_tau at every unconstrained
    position, computed in `dtype`, with the program's normalising constants.

    position: {"mu": (...,), "log_tau": (...,), "theta_trans": (..., J)}.
    Returns an array shaped like `mu`."""
    dt = np.dtype(dtype).type
    mu = np.asarray(position["mu"], dt)
    lt = np.asarray(position["log_tau"], dt)
    th = np.asarray(position["theta_trans"], dt)
    y, sigma = np.asarray(data["y"], dt), np.asarray(data["sigma"], dt)
    tau = np.exp(lt)
    z = tau / dt(TAU_SCALE)
    log_half_cauchy = (dt(math.log(2.0 / (math.pi * TAU_SCALE))) - np.log1p(z * z)).astype(dt)
    loc = mu[..., None] + tau[..., None] * th
    lp = (_log_normal(mu, dt(0.0), dt(MU_SCALE), dt)
          + log_half_cauchy + lt
          + np.sum(_log_normal(th, dt(0.0), dt(1.0), dt), axis=-1, dtype=dt)
          + np.sum(_log_normal(y, loc, sigma, dt), axis=-1, dtype=dt))
    return (-lp).astype(dt)


def moments_ref(data: dict, grid: int = 40001) -> dict:
    """{site: (posterior mean, posterior variance)} for `CHECK_SITES`, in
    float64, by trapezoid quadrature over log_tau in [-30, 10] with mu and
    theta_trans integrated out in closed form. The density of log_tau is
    the half-Cauchy's times tau (the Jacobian): it falls as tau below and
    as tau^-9 above, so the range holds all but e^-30 of the mass."""
    y = np.asarray(data["y"], np.float64)
    s2 = np.asarray(data["sigma"], np.float64) ** 2
    lt = np.linspace(-30.0, 10.0, grid)
    v = np.exp(2.0 * lt)[:, None] + s2[None, :]            # (G, J)
    prec = 1.0 / MU_SCALE**2 + np.sum(1.0 / v, axis=1)      # mu | log_tau
    b = np.sum(y / v, axis=1)
    c = np.sum(y * y / v, axis=1)
    m = b / prec
    log_post = (lt - np.log1p(np.exp(2.0 * lt) / TAU_SCALE**2)  # log_tau prior
                - 0.5 * np.sum(np.log(v), axis=1)
                - 0.5 * np.log(prec)
                - 0.5 * (c - b * b / prec))
    w = np.exp(log_post - log_post.max())
    w /= np.trapezoid(w, lt)

    def expect(f):
        return float(np.trapezoid(w * f, lt))

    e_mu, e_mu2 = expect(m), expect(m * m + 1.0 / prec)
    e_lt, e_lt2 = expect(lt), expect(lt * lt)
    return {"mu": (e_mu, e_mu2 - e_mu**2), "log_tau": (e_lt, e_lt2 - e_lt**2)}


def flops_per_grad(spec: dict) -> int:
    """Floating-point operations of one value-and-gradient of the potential
    of one chain at its unconstrained position, counting exp, log1p and a
    division as one operation each and precomputing only what does not
    depend on the position (1/sigma, the normalising constants):

    value, 8 per school (tau * theta, + mu, y - loc, * 1/sigma, square, add,
    theta^2, add) and 9 more (exp(log_tau); mu's prior, 3; tau's prior,
    tau / 5, square, log1p, add; the Jacobian, 1);
    gradient, 6 per school (r / sigma again, theta - tau * s as 2, the sum
    for mu, the product and sum for tau as 2) and 8 more (mu's prior, 2;
    tau's prior, 2 tau / 25 / (1 + z^2) as 3 and its add; the chain rule
    through exp, 1; the Jacobian, 1). So 14 J + 17."""
    return 14 * spec["J"] + 17


def leapfrog_bytes_per_step(spec: dict) -> int:
    """Bytes one chain moves through one leapfrog step of the integrator
    op, from its operands and results (float32 or int32, 4 bytes each):
    position, momentum and inverse mass in, position and momentum out
    (5 D), step size and step count in, potential out (3)."""
    return 4 * (5 * spec["D"] + 3)
