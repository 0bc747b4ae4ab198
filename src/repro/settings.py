"""One typed surface for every ``REPRO_*`` environment knob.

The knobs accumulated across the kernel, contraction-planner, MCMC,
compilation-cache, serving, and bench layers used to be raw
``os.environ.get`` calls scattered over half a dozen modules, each with its
own default literal and truthiness convention. This module is the single
registry: every knob is declared once (name, default, type, one-line
effect), every library read goes through a typed getter here, and the
environment-variable table in ``docs/backends.md`` is *checked against*
this registry (`render_env_table`; the docs page doctests the comparison,
so the table cannot drift from the code).

Semantics, unchanged from the scattered reads this replaces:

* the environment always wins — getters read ``os.environ`` at **call
  time**, never at import time, so tests and launchers may flip a knob
  mid-process;
* boolean knobs treat ``0`` / ``false`` / ``off`` (case-insensitive) as
  false and anything else as true;
* unknown knob names raise ``KeyError`` immediately — a typo'd getter is a
  bug, not a silent default.

Example::

    >>> from repro import settings
    >>> settings.get_bool("REPRO_MCMC_FUSED")     # default "1" -> True
    True
    >>> import os; os.environ["REPRO_MCMC_FUSED"] = "off"
    >>> settings.get_bool("REPRO_MCMC_FUSED")     # env wins, read at call time
    False
    >>> del os.environ["REPRO_MCMC_FUSED"]
    >>> settings.get_int("REPRO_ENUM_PLAN_BB")
    10
    >>> settings.get_raw("REPRO_TYPO")
    Traceback (most recent call last):
        ...
    KeyError: "unknown settings knob 'REPRO_TYPO' (see repro.settings.KNOBS)"
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

_FALSE = ("0", "false", "off")


@dataclass(frozen=True)
class Knob:
    """One documented environment knob."""

    name: str
    default: Optional[str]  # None = unset by default
    kind: str  # "str" | "bool" | "int" | "float"
    effect: str  # one-line description (the docs table's "effect" column)
    choices: Optional[Tuple[str, ...]] = None
    deprecated: bool = False

    @property
    def default_display(self) -> str:
        return "unset" if self.default is None else f"`{self.default}`"


# ---------------------------------------------------------------------------
# the registry — one row per knob, in docs-table order
# ---------------------------------------------------------------------------

_KNOB_ROWS = [
    Knob("REPRO_KERNEL_BACKEND", "auto", "str",
         "kernel backend: `tpu`, `interpret`, `reference`/`ref`, or `auto` "
         "(platform default)",
         choices=("tpu", "interpret", "reference", "ref", "auto")),
    Knob("REPRO_PALLAS_INTERPRET", None, "str",
         "**deprecated** interpret-mode flag; consulting it warns "
         "(migration above)", deprecated=True),
    Knob("REPRO_MCMC_FUSED", "1", "bool",
         "`0`/`false`/`off` routes `MCMC.run` through the legacy per-chain "
         "vmap sampler instead of the fused batched driver (`ops.leapfrog` + "
         "cross-chain adaptation); per-instance override via "
         "`MCMC(..., fused=...)`"),
    Knob("REPRO_ENUM_DISPATCH", "auto", "str",
         "`auto` routes eliminations through the contraction planner; "
         "`pairwise` forces the greedy eliminator (bit-identical pre-planner "
         "path; for the Gaussian semiring, the dense sequential Schur "
         "reference)", choices=("auto", "pairwise")),
    Knob("REPRO_ENUM_CHAIN_MIN", None, "int",
         "overrides the planner's ~18-edge chain crossover; when set, chains "
         "also keep the legacy `hmm_scan` tree lowering"),
    Knob("REPRO_ENUM_CHAIN_LOWER", "auto", "str",
         "pins the chain lowering: `scan` (plan-level `lax.scan`), `tree` "
         "(`hmm_scan`; `gaussian_scan` for Gaussian chains), or `folds` "
         "(sequential `semiring_matmul` / `gaussian_combine`)",
         choices=("auto", "scan", "tree", "folds")),
    Knob("REPRO_ENUM_PLAN_BB", "10", "int",
         "max dim count for branch-and-bound elimination ordering; larger "
         "problems fall back to greedy min-cost"),
    Knob("REPRO_ENUM_PLAN_CACHE", "1", "bool",
         "`0`/`false`/`off` disables the structural plan cache (every "
         "elimination replans)"),
    Knob("REPRO_ENUM_PLAN_CACHE_SIZE", "256", "int",
         "plan-cache capacity (FIFO eviction)"),
    Knob("REPRO_COMPILATION_CACHE_MIN_COMPILE_S", "0.5", "float",
         "only compilations slower than this persist to the cache"),
    Knob("REPRO_SMC_RESAMPLE", "systematic", "str",
         "default SMC resampling scheme: `systematic` (the `ops.resample` "
         "sorted-uniform kernel, one shared uniform per event) or "
         "`multinomial` (`jax.random.categorical`, N independent draws — "
         "higher variance, kept for A/B checks)",
         choices=("systematic", "multinomial")),
    Knob("REPRO_SERVE_DEADLINE_MS", None, "float",
         "default per-request deadline for the HTTP serving front end "
         "(`serve/server.py`); requests whose projected queue wait exceeds "
         "it are shed with HTTP 429. Unset = no default deadline"),
    Knob("REPRO_BENCH_TOLERANCE", "0.25", "float",
         "bench-gate relative tolerance on steady-state metrics"),
    Knob("REPRO_BENCH_ABS_MS", "0.5", "float",
         "bench-gate absolute slack on `*_ms` metrics"),
    Knob("REPRO_BENCH_ABS_RATE", "0.05", "float",
         "bench-gate absolute slack on rate metrics (`shed_rate`)"),
    Knob("REPRO_BENCH_COLD_TOLERANCE", "1.0", "float",
         "bench-gate relative tolerance on cold-compile metrics"),
    Knob("REPRO_BENCH_COLD_ABS_S", "2.0", "float",
         "bench-gate absolute slack (seconds) on cold-compile metrics"),
    Knob("REPRO_BENCH_COLD_BUDGET_S", "13.85", "float",
         "hard ceiling on the T=512 chain's cold compile in "
         "`benchmarks/enum_ve.py`"),
]

KNOBS: Dict[str, Knob] = {k.name: k for k in _KNOB_ROWS}


def _knob(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(
            f"unknown settings knob {name!r} (see repro.settings.KNOBS)"
        ) from None


# ---------------------------------------------------------------------------
# typed getters — env wins, read at call time
# ---------------------------------------------------------------------------


def get_raw(name: str) -> Optional[str]:
    """The raw environment value, or the registered default (possibly None).
    The env var always wins; it is read on every call, never cached."""
    knob = _knob(name)
    env = os.environ.get(name)
    return env if env is not None else knob.default


def is_set(name: str) -> bool:
    """Whether the knob is explicitly set in the environment."""
    _knob(name)
    return name in os.environ


def get_str(name: str) -> str:
    value = get_raw(name)
    if value is None:
        raise ValueError(f"knob {name} has no default and is not set")
    return value


def get_bool(name: str) -> bool:
    """False iff the effective value is ``0``/``false``/``off`` (case-
    insensitive) — the truthiness convention every boolean knob shares."""
    value = get_raw(name)
    return value is not None and value.strip().lower() not in _FALSE


def get_int(name: str) -> int:
    return int(get_str(name))


def get_float(name: str) -> float:
    return float(get_str(name))


def get_optional_float(name: str) -> Optional[float]:
    value = get_raw(name)
    return None if value is None or value.strip() == "" else float(value)


# ---------------------------------------------------------------------------
# documentation surface
# ---------------------------------------------------------------------------


def describe() -> List[Dict[str, str]]:
    """Registry rows as dicts (name/default/kind/effect) in table order."""
    return [
        {"name": k.name, "default": k.default_display, "kind": k.kind,
         "effect": k.effect}
        for k in _KNOB_ROWS
    ]


def render_env_table() -> str:
    """The environment-variable reference as a markdown table — the exact
    text between the ``settings:begin``/``settings:end`` markers in
    ``docs/backends.md``. That page doctests the comparison, so the docs
    table is mechanically locked to this registry."""
    lines = [
        "| variable | default | effect |",
        "|----------|---------|--------|",
    ]
    for k in _KNOB_ROWS:
        lines.append(f"| `{k.name}` | {k.default_display} | {k.effect} |")
    return "\n".join(lines)


def documented_env_table(markdown_text: str) -> str:
    """Extract the table between the settings markers of a docs page (used
    by the drift check in docs/backends.md and tests/test_settings.py)."""
    begin, end = "<!-- settings:begin -->", "<!-- settings:end -->"
    if begin not in markdown_text or end not in markdown_text:
        raise ValueError("docs page is missing the settings table markers")
    return markdown_text.split(begin, 1)[1].split(end, 1)[0].strip()
