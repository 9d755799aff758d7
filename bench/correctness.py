"""Whether a run's served GRPO steps are right: the numbers compared.

For every job of a run the harness keeps what the timed path produced in
its first steps: the packed batches of steps 1-3 (prompts, served tokens,
their behaviour log-probabilities, advantages), their losses, the norm of
each leaf's first gradient as AdamW holds it after step 1, and the norm of
each leaf's change after three updates. The plain reference starts from the
same seeded weights, follows the same three updates in float32, and reads
its own logits on the served sequences of steps 1-2.

GENERATE is compared on steps 1-2, whose weights the program and the
reference share (the seeded draw, then one update): from the second update
on, the two trajectories part by the bf16 rounding of each update, and on
the chip that, not GENERATE, set the log-probability gap of steps 3-4.

Numbers (each the worst over the run's jobs):

- ``gen_logp_err``: the widest gap between a served token's behaviour
  log-probability and the reference's (GENERATE: prefill and decode
  through the KV cache or the SSM state); ``gen_logp_rms``: the root mean
  square of the same gaps. A log-probability of the token served is
  defined whichever way it was drawn, so sampled tokens compare as they
  are served.
- ``loss_err``: the widest gap between a step's loss and the reference's.
- ``grad_err``: over leaves, the gap between the norms of the program's and
  the reference's first gradient, over the larger of the reference leaf's
  norm and the median leaf's (UPDATE_ACTOR); ``grad_med``: the median
  leaf's gap, the same way.
- ``delta_err``: the worst leaf's gap for each leaf's change after three
  updates, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's, which move by round-off alone.
- ``init_mismatch``: leaves of a job's initial weights that differ from
  the reference's draw from the same seed (exact: every number above
  assumes that the two start alike).
- ``switch_mismatch``: leaves of a job's state that differ after a context
  switch from what they held before it (state plane; exact).

Which of these a cell compares, and against what limit, is its
configuration file's ``limits``; the rest are printed beside them.

``calibration_numbers`` also runs the reference in float8 in the
program's place (the control), and plants the faults: half a batch left out
of an update, an altered token, a step that leaves the state unchanged.
These are read by ``bench/calibrate.py``, never by a benchmark run.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import grpo, mamba2, qwen2, weights
from bench.reference.numerics import F32, FP8

FAMILIES = {"qwen2": qwen2, "mamba2": mamba2}
REFERENCE_STEPS = 3      # updates the reference follows
GENERATE_STEPS = 2       # GENERATE steps compared (see the module docstring)
ROWS = 4                 # rows per reference block
QUIET_LEAF = 1e-3        # gradient share below which a leaf moves by round-off


def family(cfg: dict):
    return FAMILIES[cfg["family"]]


class Reference:
    """The plain reference of one configuration and traffic mix."""

    def __init__(self, cfg: dict, traffic: dict):
        self.mod = family(cfg)
        self.arch = self.mod.arch(cfg)
        self.table = self.mod.leaf_table(self.arch)
        self.opt = traffic["optimizer"]
        self.clip_eps = traffic["grpo"]["clip_eps"]
        self.init = jax.jit(partial(weights.make_params, self.table))
        # the program draws its weights op by op; so does this, bit for bit
        self.init_eager = partial(weights.make_params, self.table)
        self.fwd = {n.name: partial(self.mod.forward, a=self.arch, num=n)
                    for n in (F32, FP8)}
        self._stats = {name: jax.jit(
            lambda p, t, pr, f=f: grpo.next_token_stats(f(p, t), t, pr))
            for name, f in self.fwd.items()}

    def stats(self, params, tokens, probes, num: str) -> Dict[str, np.ndarray]:
        out: Dict[str, list] = {}
        for i in range(0, tokens.shape[0], ROWS):
            blk = self._stats[num](params, jnp.asarray(tokens[i:i + ROWS]),
                                   jnp.asarray(probes[i:i + ROWS]))
            for k, v in blk.items():
                out.setdefault(k, []).append(np.asarray(v))
        return {k: np.concatenate(v) for k, v in out.items()}

    def follow(self, seed: int, batches: List[dict], num: str = "f32",
               rows: Optional[slice] = None, probe_fn=None,
               read_stats: bool = True) -> dict:
        """Three updates from the seeded weights on ``batches[:3]``; with
        ``read_stats``, the float32 stats of batches 1-2 at the weights
        their GENERATE ran with. ``rows`` keeps part of each batch in the
        updates (a planted fault). ``probe_fn(k, params)`` gives the probe
        ids of batch k (default: its tokens)."""
        params = self.init(seed)
        mu, nu = grpo.zeros_f32(params), grpo.zeros_f32(params)
        out = {"losses": [], "stats": []}
        for k, batch in enumerate(batches, start=1):
            if read_stats and k <= GENERATE_STEPS:
                probes = batch["tokens"] if probe_fn is None \
                    else probe_fn(k, params)
                out["stats"].append(self.stats(params, batch["tokens"],
                                               probes, "f32"))
            train = {n: jnp.asarray(batch[n] if rows is None
                                    else batch[n][rows])
                     for n in ("tokens", "advantages", "loss_mask")}
            loss, grads = grpo.loss_and_grads(self.fwd[num], params, train,
                                              self.clip_eps, ROWS)
            out["losses"].append(loss)
            params, mu, nu = grpo.adamw(self.opt, params, mu, nu, grads, k - 1)
            if k == 1:
                out["grad_norms"] = [n / (1 - self.opt["b1"])
                                     for n in grpo.leaf_norms(mu)]
        del mu, nu
        out["delta_norms"] = grpo.diff_norms(params, self.init(seed))
        return out


# ----------------------------------------------------------------- numbers

def served_positions(batch: dict, prompt_len: int):
    """(rows, stats index) of every served token: the completion tokens up
    to and including the first end-of-sequence, each predicted by the logits
    one position before it."""
    alive = batch["alive"]
    served = np.ones_like(alive, bool)
    served[:, 1:] = alive[:, :-1]
    rows, cols = np.nonzero(served)
    return rows, cols + prompt_len - 1, cols


def gen_numbers(batches: List[dict], stats: List[dict], prompt_len: int,
                logp_of=None) -> dict:
    """``gen_logp_err`` (the widest gap) and ``gen_logp_rms`` (the root
    mean square) over all served tokens of the batches ``stats`` covers.
    ``logp_of(k, r, t)`` gives the log-probabilities compared (default: the
    served ones)."""
    errs = []
    for k, (b, st) in enumerate(zip(batches, stats), start=1):
        r, t, c = served_positions(b, prompt_len)
        ref_logp = st["logit_tok"][r, t] - st["lse"][r, t]
        mine = b["logprobs"][r, c] if logp_of is None else logp_of(k, r, t)
        errs.append(np.abs(mine - ref_logp))
    errs = np.concatenate(errs)
    return {"gen_logp_err": float(errs.max()),
            "gen_logp_rms": float(np.sqrt(np.mean(np.square(errs))))}


def leaf_gaps(mine, ref, keep=None) -> np.ndarray:
    """Each kept leaf's gap of norms, over the larger of that leaf's
    reference norm and the median leaf's."""
    mine, ref = np.asarray(mine, float), np.asarray(ref, float)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    floor = np.maximum(ref, np.median(ref[keep]))
    return (np.abs(mine - ref) / floor)[keep]


def leaf_gap(mine, ref, keep=None) -> float:
    """The worst leaf's gap (:func:`leaf_gaps`)."""
    return float(np.max(leaf_gaps(mine, ref, keep)))


def train_numbers(losses, grad_norms, delta_norms, ref: dict) -> dict:
    g = np.asarray(ref["grad_norms"])
    keep = g >= QUIET_LEAF * np.median(g)
    return {
        "loss_err": float(np.max(np.abs(np.asarray(losses)
                                        - np.asarray(ref["losses"])))),
        "grad_err": leaf_gap(grad_norms, g),
        "grad_med": float(np.median(leaf_gaps(grad_norms, g))),
        "delta_err": leaf_gap(delta_norms, ref["delta_norms"], keep),
    }


def job_numbers(reference: Reference, job: dict, prompt_len: int) -> dict:
    """The numbers of one job of a run, against the reference."""
    batches = [job["batches"][k] for k in range(1, REFERENCE_STEPS + 1)]
    ref = reference.follow(job["seed"], batches)
    out = gen_numbers(batches, ref["stats"], prompt_len)
    out.update(train_numbers(job["losses"], job["grad_norms"],
                             job["delta_norms"], ref))
    return out


def worst(per_job: List[dict]) -> dict:
    return {k: max(d[k] for d in per_job) for k in per_job[0]}


def calibration_numbers(reference: Reference, job: dict, prompt_len: int,
                        rng: np.random.Generator) -> dict:
    """The program's numbers, the float8 control's and the planted faults'
    for one job: keys ``program``, ``control``, ``half_batch``,
    ``altered_token`` and ``unchanged_state``.

    The control reads float8's own log-probability of each served token at
    the weights the program served it with; its training numbers follow
    three float8 updates. ``half_batch`` follows
    updates on the first half of each batch; ``altered_token`` alters one
    served token of each batch (its reported log-probability unchanged);
    ``unchanged_state`` is a step that leaves the state as it was."""
    batches = [job["batches"][k] for k in range(1, REFERENCE_STEPS + 1)]
    control_stats: List[dict] = []
    altered: Dict[int, tuple] = {}

    def probes(k, params):
        b = batches[k - 1]
        control_stats.append(reference.stats(params, b["tokens"],
                                             b["tokens"], "fp8"))
        out = np.array(b["tokens"])
        r, t, c = served_positions(b, prompt_len)
        i = int(rng.integers(len(r)))
        new = (int(b["tokens"][r[i], t[i] + 1]) + 1
               + int(rng.integers(reference.arch["V"] - 1))) \
            % reference.arch["V"]
        out[r[i], t[i] + 1] = new
        altered[k] = (int(r[i]), int(t[i]), int(c[i]))
        return out

    ref = reference.follow(job["seed"], batches, probe_fn=probes)
    program = gen_numbers(batches, ref["stats"], prompt_len)
    program.update(train_numbers(job["losses"], job["grad_norms"],
                                 job["delta_norms"], ref))

    control = gen_numbers(
        batches, ref["stats"], prompt_len,
        logp_of=lambda k, r, t: (control_stats[k - 1]["logit_tok"][r, t]
                                 - control_stats[k - 1]["lse"][r, t]))
    ctl_run = reference.follow(job["seed"], batches[:REFERENCE_STEPS], "fp8",
                               read_stats=False)
    control.update(train_numbers(ctl_run["losses"], ctl_run["grad_norms"],
                                 ctl_run["delta_norms"], ref))

    half = batches[0]["tokens"].shape[0] // 2
    half_run = reference.follow(job["seed"], batches[:REFERENCE_STEPS],
                                rows=slice(0, half), read_stats=False)
    half_batch = train_numbers(half_run["losses"], half_run["grad_norms"],
                               half_run["delta_norms"], ref)

    alt = {"gen_logp_err": 0.0}
    for k, (r, t, c) in altered.items():
        st, b = ref["stats"][k - 1], batches[k - 1]
        alt["gen_logp_err"] = max(alt["gen_logp_err"], abs(
            b["logprobs"][r, c] - (st["logit_probe"][r, t] - st["lse"][r, t])))
    unchanged = train_numbers(ref["losses"], [0.0] * len(ref["grad_norms"]),
                              [0.0] * len(ref["delta_norms"]), ref)
    detail = {"grad_ref": ref["grad_norms"], "grad_program": job["grad_norms"],
              "grad_control": ctl_run["grad_norms"],
              "grad_half_batch": half_run["grad_norms"],
              "delta_ref": ref["delta_norms"],
              "delta_program": job["delta_norms"],
              "delta_control": ctl_run["delta_norms"],
              "losses_program": job["losses"], "losses_ref": ref["losses"]}
    for k, (b, st) in enumerate(zip(batches, ref["stats"]), start=1):
        r, t, c = served_positions(b, prompt_len)
        e = np.abs(b["logprobs"][r, c] - (st["logit_tok"][r, t]
                                          - st["lse"][r, t]))
        i = int(np.argmax(e))
        detail[f"gen_step{k}"] = dict(
            n=int(e.size), mean=float(e.mean()),
            p99=float(np.quantile(e, 0.99)), max=float(e[i]),
            at=[int(r[i]), int(t[i])],
            ref_logp_at=float(st["logit_tok"][r[i], t[i]] - st["lse"][r[i], t[i]]))
    return {"program": program, "control": control, "half_batch": half_batch,
            "altered_token": {k: float(v) for k, v in alt.items()},
            "unchanged_state": unchanged, "detail": detail}
