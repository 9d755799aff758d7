"""The comparison that decides ``correct`` fails what it must, at a size
the CPU test run can hold: the float8 control reads far above the program,
and a run whose timed path is broken underneath comes out not correct for
each fault a served GRPO cell can have."""
from __future__ import annotations

import numpy as np
import pytest

import bench_tiny
from bench import correctness, harness
from repro.core import worker
from repro.rl import grpo as prog_grpo
from repro.rl import rollout as rollout_lib

LIMITS = harness.limits_for(bench_tiny.qwen2_cfg(), jobs=1)


def tiny_run(seed: int) -> harness.Run:
    run = harness.Run(bench_tiny.qwen2_cfg(), bench_tiny.traffic(), seed, 1.0)
    run.run()
    return run


def correct(run: harness.Run) -> bool:
    return harness.is_correct(harness.checks(run.numbers(), LIMITS))


@pytest.fixture(scope="module")
def sound():
    return tiny_run(3000000001)


def test_control_reads_far_above_the_program(sound):
    (rec,) = sound.job_records()
    got = correctness.calibration_numbers(
        sound.reference, rec, sound.traffic["prompt_len"],
        np.random.default_rng(1))
    assert harness.verdicts(sound.state_numbers(), got, LIMITS) == {
        "program": True, "control": False, "half_batch": False,
        "altered_token": False, "unchanged_state": False}
    prog, ctl = got["program"], got["control"]
    for name in ("gen_logp_err", "grad_err"):
        assert ctl[name] > 3 * prog[name], (name, prog, ctl)
    assert got["altered_token"]["gen_logp_err"] > 3 * prog["gen_logp_err"]
    assert got["unchanged_state"]["grad_err"] == pytest.approx(1.0)
    assert got["half_batch"]["grad_err"] > 3 * prog["grad_err"]


def test_sound_run_is_correct(sound):
    assert correct(sound), sound.numbers()


def _unchanged_state(self, batch):
    """UPDATE_ACTOR that reports its metrics and keeps the old state."""
    _, metrics = prog_grpo.compute_grads(self.params(), self.model, batch,
                                         self.grpo_cfg, None)
    return {k: float(v) for k, v in metrics.items()}


def _half_batch(update):
    def half(self, batch):
        n = batch["tokens"].shape[0] // 2
        return update(self, {k: v[:n] for k, v in batch.items()})
    return half


def _altered_token(rollout):
    def altered(model, params, prompts, rng, cfg, **kwargs):
        toks, logps, alive = rollout(model, params, prompts, rng, cfg,
                                     **kwargs)
        return toks.at[0, 3].set((toks[0, 3] + 1) % model.cfg.vocab_size), \
            logps, alive
    return altered


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_token"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    wpg = worker.WorkerProcessGroup
    if fault == "unchanged_state":
        monkeypatch.setattr(wpg, "_op_update_actor", _unchanged_state)
    elif fault == "half_batch":
        monkeypatch.setattr(wpg, "_op_update_actor",
                            _half_batch(wpg._op_update_actor))
    else:
        monkeypatch.setattr(rollout_lib, "rollout",
                            _altered_token(rollout_lib.rollout))
    assert not correct(tiny_run(4000000002))
