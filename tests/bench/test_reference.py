"""The plain references agree with the served system at reduced sizes on
the CPU, and the FLOP count with a hand count."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import correctness, flops
from bench.reference import grpo as ref_grpo
from bench.reference import numerics, weights
from repro.configs import get_config
from repro.models.registry import build_model
from repro.rl import grpo as prog_grpo
from repro.train import optimizer as prog_opt

CELLS = {"qwen2": bench_tiny.qwen2_cfg, "mamba2": bench_tiny.mamba2_cfg}


def program_model(cfg):
    prog = cfg["program"]
    return build_model(get_config(prog["registry"]).replace(
        **prog["overrides"]))


def tokens(cfg, rows=3, seq=24, seed=0):
    vocab = correctness.family(cfg).arch(cfg)["V"]
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, vocab)


@pytest.mark.parametrize("fam", sorted(CELLS))
def test_seeded_weights_equal_the_programs_bit_for_bit(fam):
    cfg = CELLS[fam]()
    ref = correctness.Reference(cfg, bench_tiny.traffic())
    for seed in (0, 2 ** 31 - 2):
        mine = program_model(cfg).init_params(jax.random.PRNGKey(seed))
        theirs = ref.init_eager(seed)
        assert jax.tree.structure(mine) == jax.tree.structure(theirs)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


@pytest.mark.parametrize("fam", sorted(CELLS))
def test_reference_forward_matches_the_program(fam):
    """bf16 program against float32 reference on the same weights: the
    gap is bf16 rounding of the activations, far under the logits' scale."""
    cfg = CELLS[fam]()
    ref = correctness.Reference(cfg, bench_tiny.traffic())
    params = ref.init(3)
    toks = tokens(cfg)
    want = ref.fwd["f32"](params, toks)
    got, _ = program_model(cfg).forward(params, {"tokens": toks})
    got = np.asarray(got, np.float32)
    scale = float(np.std(np.asarray(want)))
    assert scale > 0.1
    assert np.max(np.abs(got - np.asarray(want))) < 0.05 * scale + 0.02


@pytest.mark.parametrize("fam", sorted(CELLS))
def test_program_decode_matches_reference_forward(fam):
    """Prefill, then decode through the cache or the SSM state: each
    step's logits against the reference's full forward."""
    cfg = CELLS[fam]()
    ref = correctness.Reference(cfg, bench_tiny.traffic())
    params = ref.init(4)
    toks = tokens(cfg, rows=2, seq=20)
    want = np.asarray(ref.fwd["f32"](params, toks))
    model = program_model(cfg)
    logits, _, cache = model.forward(params, {"tokens": toks[:, :12]},
                                     return_cache=True)
    from repro.rl.rollout import _pad_cache
    cache = _pad_cache(cache, 8)
    scale = float(np.std(want))
    for t in range(12, 20):
        step, cache = model.decode_step(params, cache,
                                        {"tokens": toks[:, t:t + 1]})
        got = np.asarray(step[:, -1], np.float32)
        assert np.max(np.abs(got - want[:, t])) < 0.05 * scale + 0.02, t


def grpo_batch(cfg, seed=5):
    toks = np.asarray(tokens(cfg, rows=8, seq=16, seed=seed))
    mask = np.zeros(toks.shape, np.float32)
    mask[:, 6:] = 1.0
    rewards = np.tile([1.0, 0.0, 0.0, 1.0], 2).astype(np.float32)
    adv = np.asarray(prog_grpo.group_relative_advantages(
        jnp.asarray(rewards), 4))
    return dict(tokens=toks, loss_mask=mask, advantages=adv)


@pytest.mark.parametrize("fam", sorted(CELLS))
def test_reference_grpo_loss_and_gradient_match_the_program(fam):
    """With behaviour log-probabilities equal to the policy's own (ratio
    1), the program's GRPO loss and gradient against the reference's."""
    cfg = CELLS[fam]()
    ref = correctness.Reference(cfg, bench_tiny.traffic())
    params = ref.init(6)
    batch = grpo_batch(cfg)
    model = program_model(cfg)
    logits, _ = model.forward(params, {"tokens": jnp.asarray(batch["tokens"])})
    behave = np.zeros(batch["tokens"].shape, np.float32)
    behave[:, 1:] = np.asarray(prog_grpo.token_logprobs(logits,
                                                        batch["tokens"]))
    prog_batch = {k: jnp.asarray(v) for k, v in batch.items()}
    prog_batch["behavior_logprobs"] = jnp.asarray(behave)
    (loss, _), grads = jax.value_and_grad(prog_grpo.grpo_loss, has_aux=True)(
        params, model, prog_batch, prog_grpo.GRPOConfig())
    want_loss, want_grads = ref_grpo.loss_and_grads(
        ref.fwd["f32"], params, {k: jnp.asarray(v) for k, v in batch.items()},
        0.2, rows=4)
    assert abs(float(loss) - want_loss) < 1e-2
    mine, theirs = ref_grpo.leaf_norms(grads), ref_grpo.leaf_norms(want_grads)
    assert correctness.leaf_gap(mine, theirs) < 0.1


def test_reference_adamw_matches_the_programs_update():
    cfg = bench_tiny.qwen2_cfg()
    tr = bench_tiny.traffic()
    ref = correctness.Reference(cfg, tr)
    params = ref.init(7)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(p.size % 97), p.shape)
        * 1e-3, params)
    state = prog_opt.init(params, prog_opt.AdamWConfig(**tr["optimizer"]))
    want, _, _ = prog_opt.update(grads, state, params,
                                 prog_opt.AdamWConfig(**tr["optimizer"]))
    mu, nu = ref_grpo.zeros_f32(params), ref_grpo.zeros_f32(params)
    got, mu, nu = ref_grpo.adamw(tr["optimizer"], jax.tree.map(jnp.copy,
                                                               params),
                                 mu, nu, grads, 0)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_fp8_control_is_coarser_than_float32():
    a = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    exact = np.asarray(a) @ np.asarray(b)
    f32 = np.asarray(numerics.F32.ein("ij,jk->ik", a, b))
    fp8 = np.asarray(numerics.FP8.ein("ij,jk->ik", a, b))
    assert np.max(np.abs(f32 - exact)) < 1e-4
    assert np.max(np.abs(fp8 - exact)) > 0.05
    g = jax.grad(lambda x: numerics.FP8.ein("ij,jk->ik", x, b).sum())(a)
    assert np.all(np.isfinite(np.asarray(g)))


def test_flops_against_a_hand_count():
    """qwen2-0.5b: per layer q and o (896 x 896 each), k and v (896 x 128
    each), and three 896 x 4,864 MLP matrices: 14,909,440 weights; 24
    layers and the 151,936 x 896 tied head make 493,961,216. A position at
    0-based index t adds 4 * 24 * (t + 1) * 896 FLOPs of attention."""
    cfg = bench_tiny.load("bench", "configs", "qwen2-0.5b.json")
    a = correctness.family(cfg).arch(cfg)
    assert flops.matmul_weights("qwen2", a) == 493_961_216
    seq, rows = 256, 16
    attn = sum(4 * 24 * (t + 1) * 896 for t in range(seq))
    fwd = rows * (seq * 2 * 493_961_216 + attn)
    step = flops.step_flops("qwen2", a, rows, seq)
    assert step["GENERATE"] == fwd
    assert step["UPDATE_ACTOR"] == 3 * fwd
    assert 16.1e12 < step["GENERATE"] + step["UPDATE_ACTOR"] < 16.4e12


def test_flops_mamba2_counts_projections_and_the_state_update():
    cfg = bench_tiny.load("bench", "configs", "mamba2-2.7b.json")
    a = correctness.family(cfg).arch(cfg)
    assert a["V"] == 50288 and a["h"] == 80 and a["di"] == 5120
    per_layer = 2560 * (2 * 5120 + 2 * 128 + 80) + 5120 * 2560
    assert flops.matmul_weights("mamba2", a) == 16 * per_layer + 50288 * 2560
    assert flops.mixing_flops("mamba2", a, 0) == 5 * 16 * 80 * 64 * 128


def test_weights_table_order_is_the_tree_order():
    cfg = bench_tiny.mamba2_cfg()
    ref = correctness.Reference(cfg, bench_tiny.traffic())
    names = weights.leaf_names(ref.table)
    leaves = jax.tree_util.tree_flatten_with_path(ref.init(0))[0]
    assert names == ["/".join(k.key for k in path) for path, _ in leaves]
