"""Traffic of a served GRPO cell, made from a traffic file and ``--seed``.

A traffic file (``bench/traffic/<name>.json``) gives the job count, the
per-step shapes (prompts, group, prompt and response lengths), the
sampling temperature, the verifier's latency law, and the job's optimiser
settings. :func:`plan` turns it and a seed into every job's seeds, each
step's verifier latency and its graded rewards.

Every seed gets the same work: the shapes are fixed, and the verifier's
latencies cycle through the ``strata`` quantiles of the latency law in one
fixed order, the same for every seed. So seeds differ in which prompts,
weights, sampled tokens and outcomes a run sees, not in how much it does,
and any window holds the same verifier time whatever the seed.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import statistics
from typing import Tuple

import numpy as np

SEED_SPACE = 2 ** 64


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng([int(w) % SEED_SPACE for w in words])


def latency_quantiles(law: dict) -> Tuple[float, ...]:
    """The ``strata`` mid-quantiles of a capped lognormal law."""
    z = statistics.NormalDist()
    n = int(law["strata"])
    return tuple(min(law["cap_s"], law["median_s"]
                     * math.exp(law["sigma"] * z.inv_cdf((i + 0.5) / n)))
                 for i in range(n))


@functools.lru_cache(maxsize=None)
def cycle_order(n: int) -> Tuple[int, ...]:
    """The order in which the quantiles are spent: a stride coprime to
    ``n`` near ``0.38 n``, so that short, middling and long stalls take
    turns and any few consecutive steps spend about the law's mean."""
    stride = max(1, round(0.382 * n))
    while math.gcd(stride, n) != 1:
        stride += 1
    return tuple(i * stride % n for i in range(n))


@dataclasses.dataclass(frozen=True)
class JobPlan:
    index: int
    seed: int                 # the job's own seed (dataset, weights, sampling)
    run_seed: int
    traffic: dict

    @property
    def batch_size(self) -> int:
        return self.traffic["prompts_per_step"] * self.traffic["group_size"]

    def verifier_s(self, step: int) -> float:
        """The verifier's latency at step ``step`` (1-based), the same for
        every seed. Job j starts its cycle ``j * strata / jobs`` steps in,
        so that the jobs of a cell do not stall in step."""
        qs = latency_quantiles(self.traffic["verifier"])
        start = self.index * len(qs) // self.traffic["jobs"]
        return qs[cycle_order(len(qs))[(step - 1 + start) % len(qs)]]

    def rewards(self, step: int) -> np.ndarray:
        """Verifier outcomes of the step's rollouts: every group of
        ``group_size`` holds between 1 and group_size - 1 correct answers,
        so GRPO's group-relative advantages are never all zero."""
        g = self.traffic["group_size"]
        rng = _rng(self.run_seed, self.index, 2, step)
        out = np.zeros(self.batch_size, np.float32)
        for start in range(0, self.batch_size, g):
            right = rng.permutation(g)[:rng.integers(1, g)]
            out[start + right] = 1.0
        return out

    def advantages(self, step: int) -> np.ndarray:
        """GRPO's group-relative advantages of :meth:`rewards`."""
        g = self.traffic["group_size"]
        r = self.rewards(step).reshape(-1, g)
        return ((r - r.mean(1, keepdims=True))
                / (r.std(1, keepdims=True) + 1e-6)).reshape(-1)


def plan(traffic: dict, seed: int) -> Tuple[JobPlan, ...]:
    """One :class:`JobPlan` per job. Job seeds are 31-bit, so any whole
    ``seed`` fits the program's seed arguments."""
    return tuple(
        JobPlan(index=j,
                seed=int(_rng(seed, j, 0).integers(0, 2 ** 31 - 1)),
                run_seed=seed, traffic=traffic)
        for j in range(traffic["jobs"]))
