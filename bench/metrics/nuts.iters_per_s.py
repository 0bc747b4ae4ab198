"""nuts.iters_per_s: chain-iterations (chains x (warmup + draws) x
inferences) over the traced window's span (host clock). Moves
nuts_ess_per_s."""


def read(record):
    c = record["counters"]
    if "chain_iters" not in c:
        return None
    return c["chain_iters"] / c["span_s"]
