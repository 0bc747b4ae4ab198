"""The one place the Pallas kernels build their Mosaic compiler parameters.

Every kernel passes its grid's dimension semantics through here, so a JAX
rename of the TPU compiler-params class is a one-line change.
"""
from jax.experimental.pallas import tpu as pltpu


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic compiler params for a grid whose axes are ``"parallel"`` or
    ``"arbitrary"`` (in grid order)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
