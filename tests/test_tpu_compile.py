"""Ahead-of-time compiles of every Pallas op for a described TPU v5e chip.

Interpret mode runs a kernel body as XLA ops and cannot see what Mosaic
refuses: block shapes off the (8, 128) tiling, VMEM overflow, ops with no
Mosaic lowering. Here each op in `ops._SUPPORT` is compiled with the ``tpu``
backend for one chip of a ``v5e:2x2`` topology (described, not attached),
at the widths its engine runs on the chip, and the compiled module must
contain the kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture: only one process
at a time may load the TPU compiler library, so it must never happen at
import or collection time (several test workers import every module).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import distributions as dist
from repro.core import primitives as P
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def test_flash_attention_smollm(one_chip):
    # smollm-135m: 9 heads / 3 kv heads, head_dim 64; batch 8, seq 1024
    B, H, K, S, d = 8, 9, 3, 1024, 64
    _compile_text(
        lambda q, k, v: ops.flash_attention(q, k, v, backend="tpu"), one_chip,
        ((B, H, S, d), BF16), ((B, K, S, d), BF16), ((B, K, S, d), BF16),
    )


def test_flash_attention_grad_smollm(one_chip):
    # the train step differentiates through the kernel (reference backward)
    B, H, K, S, d = 8, 9, 3, 1024, 64

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, backend="tpu").astype(F32))

    _compile_text(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
        ((B, H, S, d), BF16), ((B, K, S, d), BF16), ((B, K, S, d), BF16),
    )


def test_categorical_logprob_smollm(one_chip):
    # one batch of the LM phase: 8 x 1024 tokens over smollm's 49152 vocab
    _compile_text(
        lambda lg, t: ops.categorical_logprob(lg, t, backend="tpu"), one_chip,
        ((8, 1024, 49152), BF16), ((8, 1024), I32),
    )


def test_ssd_scan_mamba2(one_chip):
    # mamba2-130m: 24 heads of width 64, state 128, chunk 128
    b, s, h, p, n = 2, 1024, 24, 64, 128
    _compile_text(
        lambda x, dt, A, B, C: ops.ssd_scan(x, dt, A, B, C, chunk=128, backend="tpu"),
        one_chip,
        ((b, s, h, p), BF16), ((b, s, h), F32), ((h,), F32),
        ((b, s, n), BF16), ((b, s, n), BF16),
    )


@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
@pytest.mark.parametrize("M,K,N", [(32, 32, 32), (100, 100, 100), (256, 256, 256), (8, 300, 130)])
def test_semiring_matmul(one_chip, semiring, M, K, N):
    _compile_text(
        lambda a, b: ops.semiring_matmul(a, b, semiring=semiring, backend="tpu"),
        one_chip, ((4, M, K), F32), ((4, K, N), F32),
    )


@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
@pytest.mark.parametrize("cumulative", [False, True])
def test_hmm_scan(one_chip, semiring, cumulative):
    # the long-HMM enum phase: T=512 steps, K=32 states
    _compile_text(
        lambda f: ops.hmm_scan(f, semiring=semiring, cumulative=cumulative, backend="tpu"),
        one_chip, ((512, 32, 32), F32),
    )


@pytest.mark.parametrize("n", [4096, 65536])
def test_resample(one_chip, n):
    _compile_text(
        lambda lw, u0: ops.resample(lw, u0, backend="tpu"), one_chip,
        ((n,), F32), ((), F32),
    )


def test_gaussian_combine(one_chip):
    T, d = 256, 4
    mat, vec = ((T, d, d), F32), ((T, d), F32)
    _compile_text(
        lambda *x: ops.gaussian_combine(x[:6], x[6:], backend="tpu"), one_chip,
        *([mat, mat, mat, vec, vec, ((T,), F32)] * 2),
    )


@pytest.mark.parametrize("d", [1, 4])
def test_gaussian_scan(one_chip, d):
    # a T=512 linear-Gaussian chain: scalar (the Kalman model chip_smoke.py
    # runs) and width 4
    T = 512
    mat, vec = ((T, d, d), F32), ((T, d), F32)
    _compile_text(
        lambda *x: ops.gaussian_scan(x, backend="tpu"), one_chip,
        mat, mat, mat, vec, vec, ((T,), F32),
    )


def _schools_potential():
    """Flat potential of the 64-school non-centered model (D = 66), built
    the way the fused MCMC driver builds it."""
    from jax.flatten_util import ravel_pytree

    from repro.infer.util import initialize_model

    gen = np.random.default_rng(0)
    y = jnp.asarray(gen.normal(5.0, 8.0, 64).astype(np.float32))
    sigma = jnp.asarray(gen.uniform(8.0, 18.0, 64).astype(np.float32))

    def schools(y, sigma):
        mu = P.sample("mu", dist.Normal(0.0, 5.0))
        log_tau = P.sample("log_tau", dist.Normal(0.0, 1.0))
        with P.plate("J", y.shape[0]):
            theta = P.sample("theta", dist.Normal(0.0, 1.0))
            P.sample("obs", dist.Normal(mu + jnp.exp(log_tau) * theta, sigma), obs=y)

    pe, _, inits = initialize_model(jax.random.PRNGKey(0), schools, (y, sigma))
    flat, unravel = ravel_pytree(inits)
    return (lambda zvec: pe(unravel(zvec))), flat.shape[0]


@pytest.mark.parametrize("max_steps", [1, 64])
def test_leapfrog_schools(one_chip, max_steps):
    pe_flat, D = _schools_potential()
    C = 1024
    _compile_text(
        lambda z, r, m, e, n: ops.leapfrog(
            z, r, m, e, n, pe_flat, max_steps=max_steps, backend="tpu"
        ),
        one_chip,
        ((C, D), F32), ((C, D), F32), ((C, D), F32), ((C,), F32), ((C,), I32),
    )
