"""Driver for many-chain NUTS: complete inferences back to back.

One unit of work is one `MCMC.run` of the program's fused NUTS (the
default kernel backend, pooled adaptation): `num_warmup` warmup iterations
and `num_samples` draws on `num_chains` chains, from a fresh key, blocked
until its draws are ready. Every inference that starts inside the window is
completed and counted; the window's span runs from the first start to the
last completion.

The check compares every draw of every inference in the window with the
configuration's float64 reference (see `check`).
"""
from __future__ import annotations

import time

import numpy as np

from lib.ess import bulk_ess

SPAN = "bench.nuts.inference"
MAX_UNITS = 4096


class Run:
    def __init__(self, config, spec: dict, traffic: dict, seed: int):
        self.config, self.spec, self.traffic, self.seed = config, spec, traffic, seed
        self.C = traffic["num_chains"]
        self.W = traffic["num_warmup"]
        self.S = traffic["num_samples"]
        self.outputs: list = []
        self.counters: dict = {}

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.infer import MCMC, NUTS

        t0 = time.perf_counter()
        self.data = self.config.make_data(self.spec, self.seed)
        model, self.args = self.config.program(self.spec, self.data)
        self.mcmc = MCMC(NUTS(model), num_warmup=self.W, num_samples=self.S,
                         num_chains=self.C)
        # one fresh key per inference, made before the window; key 0 warms up
        self.keys = np.asarray(jax.random.split(jax.random.PRNGKey(self.seed), MAX_UNITS + 1))
        t1 = time.perf_counter()
        jax.block_until_ready(self._infer(self.keys[0]))
        self.setup_phases = {"build_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def _infer(self, key):
        self.mcmc.run(key, *self.args)
        return (self.mcmc.get_samples(group_by_chain=True),
                self.mcmc.get_extra_fields(group_by_chain=True))

    @property
    def num_traces(self) -> int:
        return self.mcmc.num_traces

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float) -> None:
        import jax

        self.outputs = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if len(self.outputs) >= MAX_UNITS:
                raise RuntimeError(f"more than {MAX_UNITS} inferences in one window")
            with jax.profiler.TraceAnnotation(SPAN):
                out = jax.block_until_ready(self._infer(self.keys[len(self.outputs) + 1]))
            self.outputs.append(out)
        self.span_s = time.perf_counter() - t0

    # -- after the window ----------------------------------------------------
    def finish(self) -> None:
        """Bring the draws to the host, free the program's state, and count."""
        self.draws = [({k: np.array(v) for k, v in s.items()},
                       {k: np.array(v) for k, v in e.items()}) for s, e in self.outputs]
        self.outputs = []
        self.mcmc = None
        # the unconstrained positions of the draws, which the check and the
        # ESS read
        self.positions = [self.config.unconstrained(d) for d, _ in self.draws]
        sites = self.config.CHECK_SITES
        ess = [min(bulk_ess(u[s]) for s in sites) for u in self.positions]
        steps = sum(int(e["num_steps"].sum()) for _, e in self.draws)
        self.counters = {
            "inferences": len(self.draws),
            "span_s": self.span_s,
            "ess_sum": float(sum(ess)),
            "ess_each": ess,
            "chain_iters": self.C * (self.W + self.S) * len(self.draws),
            "leapfrog_steps": steps,
            "flops_per_step": self.config.flops_per_grad(self.spec),
            "bytes_per_step": self.config.leapfrog_bytes_per_step(self.spec),
        }

    def plant(self, fault: str) -> None:
        """Break the window's answers as a faulty program would (tests and
        the control runs only). ``frozen``: every transition returns its
        state unchanged, so each chain reports its starting point, drawn as
        the MCMC engine draws it (uniform on [-2, 2] in every unconstrained
        coordinate), with its potential; ``half``: half of the chains do;
        ``altered``: one draw's `mu` moves by one without its potential."""
        gen = np.random.default_rng([self.seed, 3])
        for (d, e), u in zip(self.draws, self.positions):
            if fault in ("frozen", "half"):
                stuck = self.C if fault == "frozen" else self.C // 2
                start = {k: np.broadcast_to(gen.uniform(-2.0, 2.0, (stuck, 1) + v.shape[2:]),
                                            (stuck,) + v.shape[1:])
                         for k, v in u.items()}
                for k, v in u.items():
                    v[:stuck] = start[k]
                for k, v in self.config.constrained(start).items():
                    d[k][:stuck] = v
                e["potential_energy"][:stuck] = self.config.potential_ref(
                    start, self.data, np.float32)
            elif fault == "altered":
                d["mu"][self.C // 2, self.S // 2] += 1.0
                u["mu"][self.C // 2, self.S // 2] += 1.0
            else:
                raise ValueError(f"unknown fault {fault!r}")

    def check(self, control: bool = False) -> list:
        """[(name, reading, limit)]: each passes when reading <= limit.

        pe_gap: over every draw, the gap between the potential the timed path
        reports (the leapfrog kernel's) and the float64 reference's at that
        draw, relative to max(1, |reference|). With `control`, the reference
        computed in bfloat16 stands in for the program.

        moment_z: for `mu` and `log_tau`, the pooled chain mean of x and of
        (x - E x)^2 against the float64 quadrature, in standard errors
        across the window's chains (chains are independent, so their means
        give the error whatever the autocorrelation within a chain).

        stuck_share: the share of the window's chains whose draws never
        change over the inference, every site alike."""
        import ml_dtypes

        limits = self.spec["limits"]
        ref_moments = self.config.moments_ref(self.data)
        gaps = []
        for u, (_, e) in zip(self.positions, self.draws):
            u_ref = self.config.potential_ref(u, self.data, np.float64)
            u_got = (self.config.potential_ref(u, self.data, ml_dtypes.bfloat16)
                     if control else e["potential_energy"]).astype(np.float64)
            gaps.append(np.max(np.abs(u_got - u_ref) / np.maximum(1.0, np.abs(u_ref))))
        zs = []
        for site in self.config.CHECK_SITES:
            mean, var = ref_moments[site]
            x = np.concatenate([u[site] for u in self.positions])
            for f, want in ((x, mean), ((x - mean) ** 2, var)):
                cm = f.mean(axis=1)
                se = cm.std(ddof=1) / np.sqrt(cm.size)
                diff = abs(cm.mean() - want)
                zs.append(diff / se if se > 0 else (0.0 if diff == 0 else np.inf))
        stuck = [np.all(x == x[:, :1], axis=tuple(range(1, x.ndim)))
                 for d, _ in self.draws for x in [np.concatenate(
                     [v.reshape(self.C, self.S, -1) for v in d.values()], axis=-1)]]
        return [("pe_gap", float(max(gaps)), limits["pe_gap"]),
                ("moment_z", float(max(zs)), limits["moment_z"]),
                ("stuck_share", float(np.mean(np.concatenate(stuck))), limits["stuck_share"])]
