"""The WorkerProcessGroup's op handlers one by one, at tiny widths on the
in-process plane: what each returns, the state it leaves in its group's
StateManager, and what its execution log bills."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeSpec
from repro.core import api
from repro.core.state_manager import StateManager, Tier
from repro.core.worker import WorkerProcessGroup
from repro.launch.mesh import DevicePlane
from repro.train import optimizer as opt

from test_system import TINY

B, S = 4, 16
# an AdamW whose first step moves bf16 params by more than their rounding
FAST = opt.AdamWConfig(lr=5e-2)
# ... and one the host optimizer (no clipping, no warm-up) computes too
PLAIN = opt.AdamWConfig(lr=5e-2, grad_clip=0.0, warmup_steps=0)


@pytest.fixture(scope="module")
def sm(tmp_path_factory):
    return StateManager(node_id="ops",
                        disk_dir=str(tmp_path_factory.mktemp("ops")),
                        mesh_slice=DevicePlane().slice_for_group(0))


def _wpg(sm, dep, role="train", seed=0, adamw_cfg=None):
    spec = api.DeploymentSpec(deployment_id=dep, job_id="ops",
                              model_name="qwen2-0.5b", role=role,
                              overrides=TINY)
    wpg = WorkerProcessGroup(spec, sm, adamw_cfg=adamw_cfg)
    wpg.execute(api.make_op(spec, api.Op.INIT, seed))
    return wpg


def _bf16_close(a, b):
    """Equal up to one bf16 rounding step: two f32 computations of the same
    update may round to neighbouring bf16 values."""
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2.0 ** -7, atol=1e-6)


def _changed(before, after):
    return any(not np.array_equal(np.asarray(x, np.float32),
                                  np.asarray(y, np.float32))
               for x, y in zip(jax.tree.leaves(before),
                               jax.tree.leaves(after)))


@pytest.fixture(scope="module")
def actor(sm):
    return _wpg(sm, "actor")


@pytest.fixture(scope="module")
def batch(actor):
    """A GRPO/PPO/value training batch whose behaviour logprobs are the
    actor's own, so every ratio starts at 1."""
    kb, ka, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    b = actor.model.dummy_batch(kb, ShapeSpec("t", "train", S, B))
    behave = np.zeros((B, S), np.float32)
    behave[:, 1:] = np.asarray(actor._op_forward(b))
    vals = np.asarray(actor._op_forward(b, output="values"))
    return dict(b, behavior_logprobs=jnp.asarray(behave),
                advantages=jax.random.normal(ka, (B,)),
                value_targets=jnp.asarray(
                    vals + 0.5 * np.asarray(jax.random.normal(kv, (B, S)))),
                old_values=jnp.asarray(vals))


@pytest.mark.parametrize("role,has_opt", [("train", True), ("critic", True),
                                          ("rollout", False)])
def test_init_registers_optimizer_state_for_trained_roles(sm, role, has_opt):
    wpg = _wpg(sm, f"init-{role}", role=role)
    params = sm.keys_for(wpg.job_prefix, "params")
    assert params and all(sm.entries[k].tier == Tier.DEVICE for k in params)
    assert bool(sm.keys_for(wpg.job_prefix, "opt")) == has_opt
    assert wpg.resident()


@pytest.mark.parametrize("output,shape", [("logprobs", (B, S - 1)),
                                          ("values", (B, S))])
def test_forward_readouts(actor, batch, output, shape):
    out = np.asarray(actor._op_forward(batch, output=output))
    assert out.shape == shape
    assert np.isfinite(out).all()
    if output == "logprobs":
        assert (out <= 0).all()
    else:
        assert ((out > -1) & (out < 1)).all()


@pytest.mark.parametrize("objective,metric", [("grpo", "pg_loss"),
                                              ("ppo", "pg_loss"),
                                              ("value", "value_loss")])
def test_forward_backward_objectives(actor, batch, objective, metric):
    if objective == "ppo":
        batch = dict(batch, advantages=jnp.broadcast_to(
            batch["advantages"][:, None], (B, S)))
    out = actor._op_forward_backward(batch, objective=objective)
    grads = out["grads"]
    assert (jax.tree.structure(grads)
            == jax.tree.structure(actor._params_template()))
    leaves = [np.asarray(g, np.float32) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(g).all() for g in leaves)
    assert any(np.abs(g).max() > 0 for g in leaves)
    assert {"loss", metric} <= set(out["metrics"])
    assert np.isfinite(float(out["metrics"]["loss"]))


@pytest.mark.parametrize("handler,kwargs", [
    ("_op_forward", {"output": "entropy"}),
    ("_op_forward_backward", {"objective": "dpo"})])
def test_unknown_readout_or_objective_is_named(actor, batch, handler, kwargs):
    (name,) = kwargs.values()
    with pytest.raises(ValueError, match=repr(name)):
        getattr(actor, handler)(batch, **kwargs)


@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
def test_optim_step_matches_adamw_on_device_and_host(sm, batch, host):
    wpg = _wpg(sm, f"optim-{host}", adamw_cfg=PLAIN)
    params = jax.tree.map(np.asarray, wpg.params())
    grads = wpg._op_forward_backward(batch)["grads"]
    res = wpg._op_optim_step(grads, host=host)
    assert res["step"] == 1
    got = wpg.params()
    assert _changed(params, got)
    want, _, _ = opt.update(grads, opt.init(params, PLAIN), params, PLAIN)
    jax.tree.map(_bf16_close, got, want)


def test_update_actor_equals_forward_backward_then_optim_step(sm, batch):
    fused = _wpg(sm, "fused", seed=5, adamw_cfg=FAST)
    split = _wpg(sm, "split", seed=5, adamw_cfg=FAST)
    before = jax.tree.map(np.asarray, fused.params())
    fused._op_update_actor(batch)
    grads = split._op_forward_backward(batch, objective="grpo")["grads"]
    assert split._op_optim_step(grads)["step"] == 1
    assert int(fused.opt_state().step) == 1
    assert _changed(before, fused.params())
    jax.tree.map(_bf16_close, fused.params(), split.params())


@pytest.mark.parametrize("fails", [False, True], ids=["returns", "raises"])
def test_execute_bills_only_ops_that_return(actor, batch, fails):
    kwargs = {"output": "entropy"} if fails else {}
    qop = api.make_op(actor.spec, api.Op.FORWARD, batch, **kwargs)
    n = actor.exec_log.total()
    if fails:
        with pytest.raises(ValueError):
            actor.execute(qop)
        assert actor.exec_log.total() == n
    else:
        actor.execute(qop)
        assert actor.exec_log.total() == n + 1
        op, seconds = actor.exec_log[-1]
        assert op == "forward" and seconds > 0


def test_checkpoint_round_trip_into_a_fresh_deployment(sm, tmp_path):
    src = _wpg(sm, "ckpt-src", seed=11)
    path = src.execute(api.make_op(src.spec, api.Op.SAVE_CHECKPOINT,
                                   str(tmp_path / "ck"), step=7))
    dst = _wpg(sm, "ckpt-dst", role="rollout", seed=12)
    assert _changed(src.params(), dst.params())
    meta = dst.execute(api.make_op(dst.spec, api.Op.LOAD_CHECKPOINT, path))
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(src.params()),
                    jax.tree.leaves(dst.params())):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))
