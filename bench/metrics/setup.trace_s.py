"""setup.trace_s: the seconds JAX spent tracing the program to a jaxpr and
lowering it to MLIR during the set-up's warm inference (the `mcmc.run` span
just before the window's, children included; each event's own seconds, so
work nested in other work counts once).
The persistent compilation cache saves none of it. Moves setup_s."""
from lib.spans import mcmc_runs

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")


def read(record):
    runs = mcmc_runs(record)
    if runs is None or runs[0] is None:
        return None
    events = runs[0]["jax_events"]
    return sum(events.get(e, 0.0) for e in EVENTS)
