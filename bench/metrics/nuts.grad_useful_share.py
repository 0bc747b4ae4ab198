"""nuts.grad_useful_share: the leapfrog steps the chains took over the
value-and-gradient evaluations the leapfrog op made for them, in percent,
over the window's inferences, warmup and draws alike (the fused driver's
counters `leapfrog_steps` and `grad_evals`, read from the program's
`mcmc.run` spans). A step needs one evaluation; the rest is paid for frozen
chains, padded calls and the op's fixed evaluations. Moves nuts_ess_per_s."""
from lib.spans import window_counts


def read(record):
    c = window_counts(record, ("leapfrog_steps", "grad_evals"))
    if c is None or c["grad_evals"] <= 0:
        return None
    return 100.0 * c["leapfrog_steps"] / c["grad_evals"]
