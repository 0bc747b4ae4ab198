"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps per the assignment.

The dispatch layer in kernels/ops.py resolves to the `reference` backend on
CPU, so these tests pin backend="interpret" to keep exercising the actual
Pallas kernel bodies (interpret mode) against the oracles.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (
    categorical_logprob,
    flash_attention,
    hmm_scan,
    semiring_matmul,
    ssd_scan,
)
from repro.kernels.ref import (
    categorical_logprob_ref,
    flash_attention_ref,
    hmm_scan_ref,
    semiring_matmul_ref,
    ssd_scan_ref,
)

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,H,K,S,d", [
    (1, 4, 4, 128, 32),   # MHA
    (2, 8, 2, 256, 64),   # GQA 4:1
    (1, 8, 1, 128, 64),   # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, K, S, d, dtype):
    q = jax.random.normal(KEY, (B, H, S, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, K, S, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, K, S, d), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, backend="interpret")
    ref = flash_attention_ref(q, k, v)
    atol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert jnp.allclose(out.astype(jnp.float32), ref.astype(jnp.float32), atol=atol)


def test_flash_attention_noncausal():
    q = jax.random.normal(KEY, (1, 2, 128, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 128, 32))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64, backend="interpret")
    ref = flash_attention_ref(q, k, v, causal=False)
    assert jnp.allclose(out, ref, atol=1e-4)


@pytest.mark.parametrize("T,V", [(64, 1000), (100, 5000), (256, 2048), (7, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_categorical_logprob_sweep(T, V, dtype):
    logits = (jax.random.normal(KEY, (T, V)) * 3).astype(dtype)
    toks = jax.random.randint(jax.random.fold_in(KEY, 3), (T,), 0, V)
    lp = categorical_logprob(logits, toks, block_t=32, block_v=512, backend="interpret")
    ref = categorical_logprob_ref(logits, toks)
    assert jnp.allclose(lp, ref, atol=1e-3)


def test_categorical_logprob_batched_shape():
    logits = jax.random.normal(KEY, (2, 8, 100))
    toks = jax.random.randint(KEY, (2, 8), 0, 100)
    lp = categorical_logprob(logits, toks, backend="interpret")
    assert lp.shape == (2, 8)
    assert jnp.allclose(lp, categorical_logprob_ref(logits, toks), atol=1e-4)


def test_categorical_logprob_extreme_logits():
    """Online LSE must survive large-magnitude logits."""
    logits = jnp.asarray([[1e4, -1e4, 0.0, 500.0]] * 8)
    toks = jnp.asarray([0, 1, 2, 3, 0, 1, 2, 3])
    lp = categorical_logprob(logits, toks, block_t=8, block_v=2, backend="interpret")
    ref = categorical_logprob_ref(logits, toks)
    assert jnp.allclose(lp, ref, atol=1e-3)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 32, 2, 8, 4, 8),
    (2, 64, 4, 16, 8, 16),
    (1, 128, 3, 32, 16, 32),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 5), (h,)))
    B = jax.random.normal(jax.random.fold_in(KEY, 6), (b, s, n))
    C = jax.random.normal(jax.random.fold_in(KEY, 7), (b, s, n))
    y = ssd_scan(x, dt, A, B, C, chunk=chunk, backend="interpret")
    ref = ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
    assert jnp.allclose(y, ref, atol=1e-3)


def test_ssd_scan_matches_naive_recurrence():
    b, s, h, p, n = 1, 24, 2, 4, 4
    x = jax.random.normal(KEY, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 8), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 9), (h,)))
    B = jax.random.normal(jax.random.fold_in(KEY, 10), (b, s, n))
    C = jax.random.normal(jax.random.fold_in(KEY, 11), (b, s, n))
    st = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(s):
        st = st * jnp.exp(dt[:, t] * A)[..., None, None] + jnp.einsum(
            "bn,bh,bhp->bhnp", B[:, t], dt[:, t], x[:, t])
        ys.append(jnp.einsum("bn,bhnp->bhp", C[:, t], st))
    naive = jnp.stack(ys, 1)
    y = ssd_scan(x, dt, A, B, C, chunk=8, backend="interpret")
    assert jnp.allclose(y, naive, atol=1e-3)


# ---------------------------------------------------------------------------
# log-space semiring matmul + hmm_scan (enumeration hot path)
# ---------------------------------------------------------------------------


def _naive_semiring_matmul(a, b, semiring):
    """Brute-force materialized oracle (independent of the shifted-exponential
    rewrite both the kernel and kernels/ref.py use for sum-product)."""
    x = a[..., :, :, None] + b[..., None, :, :]
    if semiring == "max":
        return jnp.max(x, axis=-2)
    return jax.scipy.special.logsumexp(x, axis=-2)


@pytest.mark.parametrize("M,K,N", [
    (4, 4, 4),        # square, sub-block
    (5, 7, 3),        # non-square, odd
    (64, 64, 64),     # exact block multiple
    (33, 100, 17),    # ragged across several K blocks
])
@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
def test_semiring_matmul_interpret_vs_reference(M, K, N, semiring):
    a = jax.random.normal(KEY, (M, K)) * 3
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (K, N)) * 3
    naive = _naive_semiring_matmul(a, b, semiring)
    got_i = semiring_matmul(a, b, semiring=semiring, backend="interpret", block=32)
    got_r = semiring_matmul(a, b, semiring=semiring, backend="reference")
    assert jnp.allclose(got_i, naive, atol=1e-4)
    assert jnp.allclose(got_r, naive, atol=1e-4)
    assert jnp.allclose(got_i, got_r, atol=1e-4)


@pytest.mark.parametrize("K", [100, 256])
@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
def test_semiring_matmul_lane_tiles(K, semiring):
    """Past one tile the lane dims (K of `a`, N) tile by multiples of 128, as
    Mosaic requires; padding with the ⊕-identity keeps ragged K exact."""
    from repro.kernels.semiring import _tile

    assert _tile(K, 128, 128) in (K, 128)
    assert _tile(300, 64, 128) == 128 and _tile(300, 64, 8) == 64
    a = jax.random.normal(KEY, (K, K)) * 3
    b = jax.random.normal(jax.random.fold_in(KEY, 3), (K, K)) * 3
    got = semiring_matmul(a, b, semiring=semiring, backend="interpret")
    want = semiring_matmul(a, b, semiring=semiring, backend="reference")
    assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)
    assert jnp.allclose(got, _naive_semiring_matmul(a, b, semiring), atol=1e-4)


@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
def test_semiring_matmul_batched_broadcast(semiring):
    """Batch dims broadcast: (2,3,8,6) x (3,6,5) -> (2,3,8,5)."""
    a = jax.random.normal(KEY, (2, 3, 8, 6)) * 2
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (3, 6, 5)) * 2
    naive = _naive_semiring_matmul(a, b, semiring)
    for backend in ("interpret", "reference"):
        got = semiring_matmul(a, b, semiring=semiring, backend=backend, block=16)
        assert got.shape == (2, 3, 8, 5)
        assert jnp.allclose(got, naive, atol=1e-4), backend


def test_semiring_matmul_extreme_magnitudes():
    """The shifted-exponential rewrite must survive large-magnitude logits
    (no exp overflow: the unshifted exp(100+100) would be inf in f32) and
    fully -inf (masked-out) rows without producing nan. Spreads stay inside
    the documented ~88-nat f32 window below the row+col shift bound —
    contributions further down flush to exactly 0, which is the standard
    log-matmul-exp truncation (see semiring.py docstring)."""
    a = jnp.asarray([[100.0, -100.0], [0.0, 50.0], [-jnp.inf, -jnp.inf]])
    b = jnp.asarray([[100.0, 0.0, -50.0], [-100.0, 1.0, 2.0]])
    naive = _naive_semiring_matmul(a[:2], b, "logsumexp")
    for backend in ("interpret", "reference"):
        got = semiring_matmul(a, b, backend=backend, block=8)
        assert bool(jnp.all(jnp.isfinite(got[:2]))), backend
        assert jnp.allclose(got[:2], naive, atol=1e-3), backend
        assert bool(jnp.all(got[2] < -1e20)), backend  # -inf row stays -inf-like


@pytest.mark.parametrize("T", [1, 2, 5, 8, 9])  # odd lengths pad with the identity
@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
def test_hmm_scan_interpret_vs_reference(T, semiring):
    F = jax.random.normal(jax.random.fold_in(KEY, T), (T, 4, 4)) * 2
    want = hmm_scan_ref(F, semiring=semiring)  # sequential O(T) oracle
    for backend in ("interpret", "reference"):
        got = hmm_scan(F, semiring=semiring, backend=backend, block=16)
        assert jnp.allclose(got, want, atol=1e-4), (T, backend)


@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
def test_hmm_scan_batched_and_cumulative(semiring):
    F = jax.random.normal(KEY, (2, 7, 3, 3)) * 2
    want = hmm_scan_ref(F, semiring=semiring)
    for backend in ("interpret", "reference"):
        got = hmm_scan(F, semiring=semiring, backend=backend, block=8)
        assert got.shape == (2, 3, 3)
        assert jnp.allclose(got, want, atol=1e-4), backend
        cum = hmm_scan(F, semiring=semiring, backend=backend, block=8, cumulative=True)
        assert cum.shape == (2, 7, 3, 3)
        # every prefix of the associative scan matches the sequential fold
        for t in range(7):
            assert jnp.allclose(
                cum[:, t], hmm_scan_ref(F[:, : t + 1], semiring=semiring), atol=1e-4
            ), (t, backend)


def test_hmm_scan_chain_marginal_matches_brute_force():
    """End-to-end semantics: the semiring product over a 3-step chain equals
    explicit enumeration of all K^4 paths."""
    K, T = 3, 3
    F = jax.random.normal(KEY, (T, K, K))
    total = semiring_matmul_ref(
        jnp.zeros((1, K)), hmm_scan(F, backend="interpret", block=8)
    )
    brute = -jnp.inf
    import itertools

    for path in itertools.product(range(K), repeat=T + 1):
        lp = sum(F[t, path[t], path[t + 1]] for t in range(T))
        brute = jnp.logaddexp(brute, lp)
    got = jax.scipy.special.logsumexp(total)
    assert jnp.allclose(got, brute, atol=1e-4)


def test_semiring_validation():
    a = jnp.zeros((2, 2))
    with pytest.raises(ValueError, match="semiring"):
        semiring_matmul(a, a, semiring="min")
    with pytest.raises(ValueError, match="square"):
        hmm_scan(jnp.zeros((3, 2, 4)))


def test_max_semiring_keeps_true_neg_inf():
    """Structurally impossible transitions (log_prob == -inf) must stay -inf
    through the max-product kernel — a finite floor like NEG_INF would make
    'is this path impossible' checks diverge between backends. Exercises the
    accumulator init, K-padding, and (via odd-length hmm_scan) the semiring
    identity padding."""
    ninf = -jnp.inf
    a = jnp.asarray([[ninf, ninf], [0.0, 1.0]])
    b = jnp.zeros((2, 3))
    want = _naive_semiring_matmul(a, b, "max")  # row 0 all -inf
    for backend in ("interpret", "reference"):
        got = semiring_matmul(a, b, semiring="max", backend=backend, block=8)
        assert jnp.array_equal(jnp.isinf(got), jnp.isinf(want)), backend
        assert jnp.allclose(got[1], want[1]), backend
    # odd-length chain -> identity padding in the tree reduction
    blockedF = jnp.stack([jnp.where(jnp.eye(3, dtype=bool), 0.0, ninf)] * 5)
    for backend in ("interpret", "reference"):
        out = hmm_scan(blockedF, semiring="max", backend=backend, block=8)
        assert bool(jnp.all(jnp.isinf(out) == ~jnp.eye(3, dtype=bool))), backend
        assert bool(jnp.all(out[jnp.eye(3, dtype=bool)] == 0.0)), backend


@pytest.mark.parametrize("semiring", ["logsumexp", "max"])
def test_semiring_matmul_grad_interpret_vs_reference(semiring):
    """The Pallas op carries a custom VJP (reference backward), so gradients
    flow through the kernel backend and match the pure-jnp path — the
    enumeration engine differentiates straight through these contractions."""
    a = jax.random.normal(KEY, (5, 4)) * 2
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 3)) * 2

    def loss(backend):
        return lambda a, b: jnp.sum(
            semiring_matmul(a, b, semiring=semiring, backend=backend, block=8) ** 2
        )

    ga_r, gb_r = jax.grad(loss("reference"), argnums=(0, 1))(a, b)
    ga_i, gb_i = jax.grad(loss("interpret"), argnums=(0, 1))(a, b)
    assert jnp.allclose(ga_r, ga_i, atol=1e-4)
    assert jnp.allclose(gb_r, gb_i, atol=1e-4)


def test_hmm_scan_grad_interpret_vs_reference():
    F = jax.random.normal(KEY, (5, 3, 3))

    def loss(backend):
        return lambda F: jnp.sum(hmm_scan(F, backend=backend, block=8))

    g_r = jax.grad(loss("reference"))(F)
    g_i = jax.grad(loss("interpret"))(F)
    assert bool(jnp.all(jnp.isfinite(g_i)))
    assert jnp.allclose(g_r, g_i, atol=1e-4)
