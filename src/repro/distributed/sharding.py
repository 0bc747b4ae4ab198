"""Sharding rules: parameter / optimizer / activation PartitionSpecs.

Scheme (DESIGN.md §6) — 2D "FSDP + TP" over mesh axes ("data", "model"),
with an optional leading "pod" axis that extends *data* parallelism across
pods (params replicated across pods; the cross-pod gradient all-reduce is
the only DCN collective per step):

* every weight is sharded on "model" along its TP-parallel dim (heads /
  ffn / experts / vocab) and on "data" along the other large dim (FSDP) —
  XLA SPMD inserts per-layer all-gathers inside the scan (overlapped with
  compute) and reduce-scatters for gradients;
* optimizer moments inherit the param spec (tree_map);
* batch inputs are sharded on ("pod","data") along batch;
* KV caches shard batch on "data" and heads (or head_dim, for small-K GQA /
  MQA / MLA-latent) on "model".

Rules are (regex over param path) -> dims template, resolved against the
actual rank of each leaf (leading scan-stack dims padded with None).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

# (pattern, spec-for-trailing-dims). First match wins. Specs are given for
# the *logical* (unstacked) weight; leading stack dims get None.
_RULES: Tuple[Tuple[str, Tuple], ...] = (
    # embeddings / head
    (r"embed$", ("model", "data")),          # (V, D): vocab TP + FSDP
    (r"lm_head$", ("data", "model")),        # (D, V)
    (r"final_norm$", (None,)),
    # attention (GQA)
    (r"\bwq$", ("data", "model")),
    (r"\bwk$", ("data", "model")),
    (r"\bwv$", ("data", "model")),
    (r"\bwo$", ("model", "data")),
    (r"\bb[qkv]$", ("model",)),
    (r"[qk]_norm$", (None,)),
    # MLA
    (r"wkv_d$", ("data", None)),             # (D, r+rope): latent dims small
    (r"wk_u$", (None, "model")),             # (r, H*nope)
    (r"wv_u$", (None, "model")),             # (r, H*v)
    # dense / shared-expert MLP
    (r"\bwg$", ("data", "model")),
    (r"\bwu$", ("data", "model")),
    (r"\bwd$", ("model", "data")),
    # MoE (expert parallelism on "model")
    (r"router$", ("data", None)),
    (r"we_g$", ("model", "data", None)),     # (E, D, de)
    (r"we_u$", ("model", "data", None)),
    (r"we_d$", ("model", None, "data")),     # (E, de, D)
    # Mamba-2 SSD
    (r"in_proj$", ("data", "model")),
    (r"out_proj$", ("model", "data")),
    (r"conv_w$", (None, "model")),
    (r"gate_norm$", ("model",)),
    (r"(A_log|D_skip|dt_bias)$", (None,)),
    # RG-LRU
    (r"wx_in$", ("data", "model")),
    (r"wy_in$", ("data", "model")),
    (r"\bwa$", ("model", None, None)),       # (blocks, bw, bw)
    (r"wxg$", ("model", None, None)),
    (r"(ba|bxg|Lambda)$", ("model",)),
    # norms and anything else small
    (r"ln\d$", (None,)),
)


def _path_str(path) -> str:
    return ".".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def spec_for_param(path_str: str, ndim: int, mesh: Mesh) -> P:
    """Resolve the rule table for one leaf; pad leading dims with None and
    drop axis names whose dimension would not divide (checked by caller via
    validate_divisibility)."""
    for pat, dims in _RULES:
        if re.search(pat, path_str):
            pad = ndim - len(dims)
            if pad < 0:  # scalar-ish leaf (e.g. rank < template): replicate
                return P()
            return P(*([None] * pad), *dims)
    return P()  # default: replicated


def _divisible(spec: P, shape, mesh: Mesh) -> P:
    """Drop axis assignments that don't divide the dim (e.g. 9 heads on a
    16-way model axis) — correctness first, the dry-run reports what's left."""
    out = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            out.append(None)
            continue
        size = mesh.shape[axis] if not isinstance(axis, tuple) else 1
        out.append(axis if dim % size == 0 else None)
    return P(*out)


def _canonical(spec: P) -> P:
    """Strip trailing Nones (P('data', None) == P('data') semantically). GSPMD
    reports jit output shardings in this minimal form; emitting the same form
    here keeps device_put-placed state and jit-returned state cache-identical."""
    dims = list(spec)
    while dims and dims[-1] is None:
        dims.pop()
    return P(*dims)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """Pytree of NamedShardings matching `params` (works on ShapeDtypeStructs)."""

    def leaf(path, x):
        spec = spec_for_param(_path_str(path), len(x.shape), mesh)
        spec = _divisible(spec, x.shape, mesh)
        return NamedSharding(mesh, _canonical(spec))

    return jax.tree_util.tree_map_with_path(leaf, params)


def batch_axes(mesh: Mesh):
    """The composite data-parallel axis: ('pod','data') when a pod axis
    exists, else 'data'; meshes without a 'data' axis fall back to their
    first axis (so generic SVI meshes work, not just the LM layout)."""
    if "data" not in mesh.axis_names:
        return mesh.axis_names[0]
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def data_axis_size(mesh: Mesh, axis=None) -> int:
    """Total shard count along the composite data axes (or an explicit
    axis/tuple) — the divisor every leading-dim sharding decision checks.
    One implementation shared by SVI batch sharding, MCMC chain sharding,
    activation constraints, and the serving engine's bucket placement."""
    if axis is None:
        axis = batch_axes(mesh)
    size = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        size *= mesh.shape[a]
    return size


def batch_shardings(batch: Any, mesh: Mesh) -> Any:
    dp = batch_axes(mesh)
    dp_size = data_axis_size(mesh, dp)

    def leaf(x):
        if not x.shape or x.shape[0] % dp_size != 0:
            return NamedSharding(mesh, P())  # tiny batches replicate
        return NamedSharding(mesh, P(dp, *([None] * (len(x.shape) - 1))))

    return jax.tree.map(leaf, batch)


def cache_shardings(cache: Any, cfg: ModelConfig, mesh: Mesh, *,
                    mla_mode: str = "seq") -> Any:
    """KV / SSM / LRU cache sharding: batch on 'data'; heads or head_dim on
    'model' where divisible. Cache leaves may carry a leading scan-stack dim.

    Layouts seen here (post-stack):
      attention k/v  (..., B, K, S, hd)
      MLA            (..., B, S, r) / (..., B, S, rope)
      ssd state      (..., B, h, n, p);  conv (..., B, w, c)
      rglru h        (..., B, W);        conv (..., B, w, c)
      pos            () scalar
    """
    tp = mesh.shape["model"]

    def leaf(path, x):
        name = _path_str(path)
        shape = x.shape
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        # find the batch dim: first dim whose size matches is ambiguous, so
        # key on structure: k/v rank>=4, others rank>=2; batch dim follows
        # any scan-stack dim. We mark the stack dim by name "scan".
        lead = 1 if name.startswith("scan") else 0
        dims: list = [None] * len(shape)
        if shape[lead] % mesh.shape["data"] == 0:
            dims[lead] = "data"
        if "k" == name.split(".")[-1] or name.split(".")[-1] in ("k", "v"):
            K, hd = shape[lead + 1], shape[-1]
            if K % tp == 0:
                dims[lead + 1] = "model"
            elif hd % tp == 0:
                dims[-1] = "model"
        elif name.endswith("state") or name.endswith("h"):
            if shape[-1] % tp == 0:
                dims[-1] = "model"
        elif name.endswith("c_kv") or name.endswith("k_rope"):
            # MLA latent cache: shard the SEQUENCE axis on 'model' (default).
            # Sharding the latent rank r costs a per-layer scores all-reduce
            # (the baseline, kept under mla_mode="rank"); replicating costs
            # full-cache HBM reads per device (refuted, §Perf iter 3b).
            # Sequence sharding keeps scores and cache reads local.
            if mla_mode == "rank":
                if shape[-1] % tp == 0:
                    dims[-1] = "model"
            elif len(shape) >= 2 and shape[lead + 1] % tp == 0:
                dims[lead + 1] = "model"
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(leaf, cache)


def constrain_leading_dim(x: Any, mesh: Mesh, axis=None) -> Any:
    """with_sharding_constraint `x`'s leading dim onto `axis` (default: the
    composite data axes). Scalars, non-arrays, and leading dims that don't
    divide the axis size pass through unconstrained (replication is correct,
    just not parallel). Shared by SVI batch sharding and ELBO particle
    sharding so the divisibility/spec logic lives in exactly one place."""
    if axis is None:
        axis = batch_axes(mesh)
    size = data_axis_size(mesh, axis)
    if not hasattr(x, "ndim") or x.ndim == 0 or x.shape[0] % size != 0:
        return x
    spec = P(axis, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_batch(tree: Any, mesh: Mesh) -> Any:
    """Constrain every array leaf's leading (batch) dim onto the data axes —
    the in-jit counterpart of `batch_shardings` for SVI minibatch args."""
    dp = batch_axes(mesh)
    return jax.tree.map(lambda x: constrain_leading_dim(x, mesh, dp), tree)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *, devices=None) -> Mesh:
    """The repo's one mesh constructor: every axis is ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` (the `constrain_leading_dim` path every
    ``mesh=`` engine takes) refuses; Auto axes leave placement to GSPMD
    propagation, which is what the sharding rules here are written for."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def default_mesh(axis_name: str = "data") -> Mesh:
    """1-D mesh over all local devices — the default for embarrassingly
    data-parallel workloads (MCMC chain sharding) where no TP axis is needed.
    `batch_axes` resolves it like any other mesh with a 'data' axis."""
    return make_mesh((jax.device_count(),), (axis_name,))


def shard_chains(tree: Any, mesh: Mesh) -> Any:
    """Constrain every array leaf's leading (chain) dim onto the data axes —
    MCMC's counterpart of `shard_batch` (same policy, one implementation).
    Chains whose count doesn't divide the data-axis size pass through
    replicated (correct, just not parallel), so a 4-chain run works
    unchanged on 1, 2 or 4 devices."""
    return shard_batch(tree, mesh)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# activation sharding constraints (trace-time contextvar scope)
# ---------------------------------------------------------------------------
#
# XLA's sharding propagation, left alone, can resolve the FSDP/TP conflict by
# replicating the batch dim and splitting d_model (observed in the smollm
# dry-run). The launcher installs this scope while tracing so the model can
# pin activations to (batch='data', ..., None) without importing the mesh.

import contextlib
import contextvars

_ACT_SCOPE: contextvars.ContextVar[Optional[Tuple[Mesh, Any]]] = contextvars.ContextVar(
    "repro_activation_sharding", default=None
)


@contextlib.contextmanager
def activation_sharding_scope(mesh: Mesh):
    token = _ACT_SCOPE.set((mesh, batch_axes(mesh)))
    try:
        yield
    finally:
        _ACT_SCOPE.reset(token)


def constrain_activation(x: jax.Array, *, extra: Optional[Dict[int, str]] = None) -> jax.Array:
    """Pin dim 0 (batch) to the data axes; optional {dim: axis} extras
    (e.g. {-1: 'model'} for vocab-sharded logits). No-op outside the scope."""
    ctx = _ACT_SCOPE.get()
    if ctx is None:
        return x
    mesh, dp = ctx
    dims = [None] * x.ndim
    dp_size = data_axis_size(mesh, dp)
    if x.shape[0] % dp_size == 0:
        dims[0] = dp
    if extra:
        for d, axis in extra.items():
            if x.shape[d] % mesh.shape[axis] == 0:
                dims[d] = axis
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*dims)))
