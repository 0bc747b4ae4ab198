"""Systematic resampling — Pallas TPU kernel (sorted-uniform vs cumsum).

SMC's resample step maps the sorted systematic grid u_i = (u0 + i)/N onto
the normalized-weight cumsum c (also sorted): ancestor i is

    idx[i] = #{j : c[j] <= u[i]}          (== searchsorted(c, u, 'right'))

`jnp.searchsorted` is the reference oracle; on TPU a per-element binary
search is a scalar-heavy, lane-divergent access pattern, while the count
form is a dense comparison-reduction the VPU eats whole. The kernel tiles
the (N_u, N_c) comparison plane: the u axis is grid-parallel, the c axis is
the "arbitrary" accumulation axis — each (bc, 1) cumsum tile is broadcast
against a (1, bu) grid tile, the (bc, bu) boolean plane is summed over
sublanes, and partial counts accumulate into the revisited output block
(same init-at-first / dwell-on-last idiom as `kernels/semiring.py`).

Layout note: c rides the sublane axis ((bc, 1) blocks) and u the lane axis
((1, bu) blocks) so the broadcast-compare and the axis-0 reduction are both
layout-natural — no in-kernel transposes. Padding uses c = 2.0 (> any u,
never counted) and u = -1.0 (counts sliced off).

Clipping to N-1 and the cumsum/grid construction live in `ops.resample`,
which shares them bit-for-bit with the reference backend.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .compiler import compiler_params


def _count_kernel(c_ref, u_ref, o_ref, *, nc: int):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    c = c_ref[...]  # (bc, 1) cumsum tile, sublane-major
    u = u_ref[...]  # (1, bu) grid tile, lane-major
    o_ref[...] += jnp.sum((c <= u).astype(jnp.int32), axis=0, keepdims=True)


def resample_counts_tiled(
    c: jax.Array,  # (N,) normalized-weight cumsum (sorted, c[-1] ~= 1)
    u: jax.Array,  # (M,) systematic grid (sorted, in [0, 1))
    *,
    block_u: int = 256,
    block_c: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """counts[i] = #{j : c[j] <= u[i]} as int32, shape (M,).

    1-D only; `kernels/ops.resample` builds the inputs, clips the counts to
    valid ancestor indices, and adds backend dispatch."""
    (n,) = c.shape
    (m,) = u.shape
    bu, bc = min(block_u, m), min(block_c, n)
    mp, np_ = -(-m // bu) * bu, -(-n // bc) * bc
    if mp != m:
        u = jnp.pad(u, (0, mp - m), constant_values=-1.0)
    if np_ != n:
        c = jnp.pad(c, (0, np_ - n), constant_values=2.0)
    nc = np_ // bc
    grid = (mp // bu, nc)

    out = pl.pallas_call(
        functools.partial(_count_kernel, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, 1), lambda iu, jc: (jc, 0)),
            pl.BlockSpec((1, bu), lambda iu, jc: (0, iu)),
        ],
        out_specs=pl.BlockSpec((1, bu), lambda iu, jc: (0, iu)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.int32),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(c.reshape(np_, 1), u.reshape(1, mp))
    return out[0, :m]
