"""Driver for particle filtering: complete SMC sweeps back to back.

One unit of work is one `SMC.run` of the program's particle filter over the
configuration's whole series (``num_particles`` particles, T steps, the
traffic's ESS threshold and resampling method), from a fresh key, with
what the check reads: log Z-hat, `filtering_means()`, `ess_history()` and
the per-step ``resampled`` flags, which stay on the device until the window
ends. The window's first `HISTORY_SWEEPS` sweeps also keep their whole
per-particle history (latents, incremental log-weights and weights); the
rest drop it, since it is T x N floats a sweep.

Sweeps are dispatched as JAX dispatches them, asynchronously, with about
`QUEUE_S` seconds of them (and at least one) waiting in the device's queue
behind the one that runs, so the host's work between sweeps, and any stall
of the host shorter than that, leaves the device busy. A sweep starts when
the ones ahead of it end; every sweep expected to start inside the window
(from the last sweep's duration) is dispatched, completed and counted. A
window holds at least the `CHECKED_SWEEPS` sweeps the check reads, and its
span runs from the first dispatch to the last completion.

The check (see `check`) compares a fixed number of sweeps with the
configuration's float64 reference, so it is as strict whatever a window
holds. `planted` breaks the program underneath the timed path, for the
tests and `bench/control.py`.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

from lib.jaxpr_count import calls_per_run

SPAN = "bench.smc.sweep"
MAX_SWEEPS = 8192
HISTORY_SWEEPS = 2
CHECKED_SWEEPS = 2
# seconds of sweeps kept in the device's queue behind the running one
QUEUE_S = 1.0
# the program's resampling op, by the name of its jitted function
RESAMPLE_OP = "_resample"
FAULTS = ("shifted", "no_reset", "half_step", "frozen", "altered", "half_mean")
# what the window keeps of the first sweeps alone: (T, N) arrays
HISTORY = ("h", "incr", "lw")


class Run:
    def __init__(self, config, spec: dict, traffic: dict, seed: int):
        self.config, self.spec, self.traffic, self.seed = config, spec, traffic, seed
        self.N = traffic["num_particles"]
        self.T = spec["T"]
        self.outputs: list = []
        self.counters: dict = {}

    # -- set-up --------------------------------------------------------------
    def _engine(self):
        from repro.infer import SMC

        model_init, model_step = self.config.program(self.spec)
        return SMC(model_init, model_step, num_particles=self.N,
                   ess_threshold=self.traffic["ess_threshold"],
                   resample_method=self.traffic["resample_method"])

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        self.data = self.config.make_data(self.spec, self.seed)
        self.ys = jnp.asarray(self.data["y"])
        self.engine = self._engine()
        # one fresh key per sweep, made before the window; key 0 warms up
        self.keys = np.asarray(jax.random.split(jax.random.PRNGKey(self.seed), MAX_SWEEPS + 1))
        t1 = time.perf_counter()
        jax.block_until_ready(self._sweep(self.engine, self.keys[0], True))
        self.setup_phases = {"build_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def _sweep(self, engine, key, history: bool) -> dict:
        engine.run(key, self.ys)
        r = engine.result
        out = {"log_z": r.log_evidence,
               "mean": engine.filtering_means()[self.spec["site"]],
               "ess": engine.ess_history(),
               "resampled": r.history.resampled}
        if history:
            out["h"] = r.history.latents[self.spec["site"]]
            out["incr"] = r.history.incr_log_weight
            out["lw"] = r.history.log_weights
        return out

    @property
    def num_traces(self) -> int:
        return self.engine.num_traces

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float) -> None:
        import jax

        self.outputs = []
        queue: list = []  # dispatched and not yet waited for, oldest first
        done_at, took = 0.0, None  # the last completion, and a sweep's length
        t0 = time.perf_counter()
        while True:
            n = len(self.outputs) + len(queue)
            full = (n >= CHECKED_SWEEPS if took is None
                    else len(queue) > max(1, math.ceil(QUEUE_S / took)))
            if full:
                self.outputs.append(jax.block_until_ready(queue.pop(0)))
                t = time.perf_counter() - t0
                took, done_at = t - done_at, t
                continue
            # a sweep dispatched now starts once those queued ahead of it end
            starts = time.perf_counter() - t0
            if took is not None:
                starts = max(starts, done_at + took * len(queue))
            if n >= CHECKED_SWEEPS and starts >= seconds:
                break
            if n >= MAX_SWEEPS:
                raise RuntimeError(f"more than {MAX_SWEEPS} sweeps in one window")
            with jax.profiler.TraceAnnotation(SPAN):
                queue.append(self._sweep(self.engine, self.keys[n + 1], n < HISTORY_SWEEPS))
        self.outputs.extend(jax.block_until_ready(queue))
        self.span_s = time.perf_counter() - t0

    # -- after the window ----------------------------------------------------
    def finish(self) -> None:
        """Bring the answers to the host, count the program's resampling
        calls, free the program's state, and count."""
        answers = [{k: np.asarray(v) for k, v in o.items()} for o in self.outputs]
        self.outputs = []
        self.history = [tuple(a.pop(k) for k in HISTORY) for a in answers[:HISTORY_SWEEPS]]
        self.answers = answers
        calls = calls_per_run(lambda key: self.engine.run(key, self.ys), self.keys[0],
                              name=RESAMPLE_OP)
        self.engine = None
        S = len(self.answers)
        # the steps that resampled: the init row never does
        resampled = int(sum(a["resampled"][1:].sum() for a in self.answers))
        self.counters = {
            "sweeps": S,
            "span_s": self.span_s,
            "num_particles": self.N,
            "T": self.T,
            "particle_steps": self.N * self.T * S,
            "resampled_steps": resampled,
            "resample_calls": None if calls is None else calls * S,
            "flops_per_particle_step": self.config.flops_per_particle_step(self.spec),
            "resample_bytes_per_call": self.config.resample_bytes_per_call(self.N),
        }
        # the sweeps whose log Z-hat and filtering means the check reads:
        # drawn from the seed among the window's
        gen = np.random.default_rng([self.seed, 1])
        self.checked = sorted(int(i) for i in gen.choice(S, CHECKED_SWEEPS, replace=False))

    def plant(self, fault: str) -> None:
        """Run the sweeps the check reads again with the program broken
        underneath (`planted`), from the same keys, and put their answers in
        place of the sound ones (`bench/control.py` only)."""
        import jax

        with planted(fault):
            engine = self._engine()
            for i in sorted(set(range(HISTORY_SWEEPS)) | set(self.checked)):
                out = {k: np.asarray(v) for k, v in jax.device_get(
                    self._sweep(engine, self.keys[i + 1], i < HISTORY_SWEEPS)).items()}
                if i < HISTORY_SWEEPS:
                    self.history[i] = tuple(out.pop(k) for k in HISTORY)
                self.answers[i] = out

    def check(self, control: bool = False) -> list:
        """[(name, reading, limit)]: each passes when reading <= limit.

        lw_gap: over the first two sweeps' whole histories, the gap between
        each incremental log-weight the program reports and the float64
        observation log-density at the reported h_t, relative to
        max(1, |reference|).

        mean_gap: over the same histories, the gap between each filtering
        mean the program reports and the float64 mean of the reported h_t
        under the reported weights, over all N particles, relative to
        max(1, |reference|).

        logz_gap: over two sweeps drawn from the seed, the largest
        |log Z-hat - log Z| in units of log Z-hat's standard deviation at
        this particle count (the configuration's ``logz_sd``), with log Z
        from the float64 grid filter.

        filter_z: over the same two sweeps, the root mean square over every
        step of (m_t - E[h_t | y_1..t]) / sqrt(Var[h_t | y_1..t] / ESS_t),
        with m_t the program's filtering mean, ESS_t its ESS, and the
        moments from the grid filter. Compared only at the particle counts
        the configuration gives it a limit for: where the filter's own
        error is as large as bfloat16's rounding of h, the control cannot
        fail it.

        With `control`, the reference computed in bfloat16 stands in for
        the program: its observation log-density for the incremental
        log-weights, its weighted means for the filtering means, its grid
        filter's log Z and means for the sweeps'."""
        import ml_dtypes

        limits = self.spec["limits"]
        K = self.spec["grid_points"]
        y = np.asarray(self.data["y"], np.float64)
        ref = self.config.grid_filter_ref(self.spec, self.data, K)
        low = (self.config.grid_filter_ref(self.spec, self.data, K, ml_dtypes.bfloat16)
               if control else None)

        def gap(got, want):
            got = np.asarray(got, np.float64)
            return float(np.max(np.abs(got - want) / np.maximum(1.0, np.abs(want))))

        lw_gaps, mean_gaps = [], []
        for (h, incr, lw), a in zip(self.history, self.answers):
            for t in range(0, self.T, 64):
                rows = slice(t, t + 64)
                want = self.config.log_obs_ref(h[rows], y[rows, None], np.float64)
                got = (self.config.log_obs_ref(h[rows], y[rows, None], ml_dtypes.bfloat16)
                       if control else incr[rows])
                lw_gaps.append(gap(got, want))
                want = weighted_mean(h[rows], lw[rows], np.float64)
                got = (weighted_mean(h[rows], lw[rows], ml_dtypes.bfloat16)
                       if control else a["mean"][rows])
                mean_gaps.append(gap(got, want))
        sd = limits["logz_sd"][str(self.N)]
        logz = [abs((low["log_z"] if control else float(self.answers[i]["log_z"]))
                    - ref["log_z"]) / sd for i in self.checked]
        checks = [("lw_gap", max(lw_gaps), limits["lw_gap"]),
                  ("mean_gap", max(mean_gaps), limits["mean_gap"]),
                  ("logz_gap", float(max(logz)), limits["logz_gap"])]
        if str(self.N) in limits["filter_z"]:
            zs = []
            for i in self.checked:
                a = self.answers[i]
                m = low["mean"] if control else np.asarray(a["mean"], np.float64)
                zs.append((m - ref["mean"]) / np.sqrt(ref["var"] / np.asarray(a["ess"], np.float64)))
            checks.append(("filter_z", float(np.sqrt(np.mean(np.square(np.concatenate(zs))))),
                           limits["filter_z"][str(self.N)]))
        return checks


def weighted_mean(h, lw, dtype) -> np.ndarray:
    """Each row's mean of `h` under the normalised weights exp(`lw`), in
    `dtype`: the weights and products in it, the sums accumulated in float64
    for float64 and in float32 otherwise, their results rounded to it."""
    acc = np.float64 if np.dtype(dtype) == np.float64 else np.float32
    lw = np.asarray(lw, dtype)
    w = np.exp((lw - lw.max(axis=-1, keepdims=True)).astype(dtype)).astype(dtype)
    total = np.sum(w, axis=-1, dtype=acc, keepdims=True).astype(dtype)
    wh = (w / total).astype(dtype) * np.asarray(h, dtype)
    return np.sum(wh, axis=-1, dtype=acc).astype(dtype)


@contextlib.contextmanager
def planted(fault: str):
    """Break the program underneath the timed path while the context is
    open (engines built inside it trace the broken program):

    shifted: every resampling hands each particle the ancestor one index on
    (``(ancestor + 1) mod N``);
    no_reset: a step that resamples keeps the particles' weights instead of
    resetting them;
    half_step: half of the particles skip each transition, keeping their
    state and weight as they came in;
    frozen: every transition returns the population as it came in;
    altered: one particle's incremental log-weight is off by 1 where it is
    produced, at every step;
    half_mean: the filtering means are taken over half of the particles."""
    import jax
    import jax.numpy as jnp

    from repro.infer import combinators as C
    from repro.infer import smc
    from repro.kernels import ops

    if fault == "shifted":
        owner, attr = ops, "resample"
        resample = ops.resample

        def broken(log_weights, u0, **kwargs):
            return (resample(log_weights, u0, **kwargs) + 1) % log_weights.shape[-1]
    elif fault == "no_reset":
        owner, attr = C.Resample, "run_population"
        step = C.Resample.run_population

        def broken(self, rng_key, params, population, *args, **kwargs):
            pop, aux = step(self, rng_key, params, population, *args, **kwargs)
            lw = pop.log_weights + jnp.where(aux.resampled, population.log_weights, 0.0)
            return C.Population(pop.carry, lw), aux._replace(
                log_weights=lw, ess=C.effective_sample_size(lw))
    elif fault in ("half_step", "frozen", "altered"):
        owner, attr = C.Program, "run_population"
        step = C.Program.run_population

        def broken(self, rng_key, params, population, *args, **kwargs):
            pop, aux = step(self, rng_key, params, population, *args, **kwargs)
            n = population.log_weights.shape[0]
            if fault == "altered":
                bump = jnp.zeros(n, jnp.float32).at[0].set(1.0)
                return C.Population(pop.carry, pop.log_weights + bump), aux._replace(
                    incr_log_weight=aux.incr_log_weight + bump,
                    log_weights=aux.log_weights + bump)
            stay = jnp.arange(n) < (n if fault == "frozen" else n // 2)

            def keep(old, new):
                return jnp.where(stay.reshape((n,) + (1,) * (new.ndim - 1)), old, new)
            return C.Population(jax.tree.map(keep, population.carry, pop.carry),
                                keep(population.log_weights, pop.log_weights)), aux
    elif fault == "half_mean":
        owner, attr = smc, "_weighted_means"
        means = smc._weighted_means

        def broken(latents, log_weights):
            half = log_weights.shape[-1] // 2
            return means(jax.tree.map(lambda x: x[:, :half], latents), log_weights[..., :half])
    else:
        raise ValueError(f"unknown fault {fault!r}; expected one of {FAULTS}")
    sound = getattr(owner, attr)
    setattr(owner, attr, broken)
    try:
        yield
    finally:
        setattr(owner, attr, sound)
