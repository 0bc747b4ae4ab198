"""The program's own telemetry (`repro.telemetry`) as the per-layer metrics
read it: the `mcmc.run` span of each inference, with the work counters the
fused driver returns and the JAX compile seconds spent inside it.

A program without the module, or without enough spans, gives None, and the
metrics that read it report nothing."""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

RUN_SPAN = "mcmc.run"


def mcmc_runs(record) -> Optional[Tuple[Optional[dict], List[dict]]]:
    """(the span before the window's, the window's spans): the window's are
    the last ``record["counters"]["inferences"]`` `mcmc.run` spans; the one
    before them is the set-up's warm inference, or None if there is none."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    n = record["counters"].get("inferences", 0)
    runs = telemetry.spans(RUN_SPAN)
    if n < 1 or len(runs) < n:
        return None
    return (runs[-n - 1] if len(runs) > n else None), runs[-n:]


def window_counts(record, keys: Sequence[str]) -> Optional[dict]:
    """{key: the counter summed on the host over the window's spans and their
    chains}, or None where a span lacks one."""
    runs = mcmc_runs(record)
    if runs is None:
        return None
    window = runs[1]
    if any(k not in s["counters"] for s in window for k in keys):
        return None
    return {k: sum(int(np.asarray(s["counters"][k]).sum(dtype=np.int64)) for s in window)
            for k in keys}
