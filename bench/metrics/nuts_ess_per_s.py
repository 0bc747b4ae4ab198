"""nuts_ess_per_s: the sum over the window's inferences of the smaller bulk
ESS of the configuration's checked sites (pooled over all chains, the
benchmark's own ESS arithmetic), over the inferences' whole wall span
(host clock, idle time included)."""


def read(record):
    c = record["counters"]
    if "ess_sum" not in c:
        return None
    return c["ess_sum"] / c["span_s"]
