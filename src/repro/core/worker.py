"""Worker-process group (WPG): one logical deployment's execution backend.

A WPG owns the jitted step functions for its model and executes admitted
operations SERIALLY (the per-WPG ordering guarantee of §4.2/§5.1); different
WPGs may run concurrently when the Scheduler admits them. Parameters and
optimizer state live under the node's StateManager as canonical entries, so
context switching (offload/load) and weight sync never touch worker code.

A WPG binds its node group's mesh slice (launch/mesh.py, read off the
group's StateManager): parameters and optimizer state are laid out with the
model's sharding rules against THAT mesh, so the jitted primitives are
per-group — two groups holding disjoint slices execute on disjoint
hardware, and migrating a WPG across groups reshards its state onto the
destination slice.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import api, telemetry
from repro.core.state_manager import StateManager, Tier
from repro.models import sharding as shd
from repro.models.registry import Model, build_model
from repro.rl import grpo, ppo as ppo_lib, rollout as rollout_lib
from repro.train import optimizer as opt, train_state as tstate
from repro.train.train_state import TrainState


class ExecLog:
    """Bounded execution log with ABSOLUTE offsets.

    Billing consumes the log through incremental cursors; an unbounded list
    leaks one tuple per op on a week-long serve plane (same failure shape
    as the executor's settled-task table before ``max_settled_tasks``).
    The ring drops the oldest entries past ``maxlen`` while ``offset``
    tracks the absolute index of the first retained entry, so cursors keep
    meaning "ops billed so far" across trims. ``len``/iteration/indexing
    cover the RETAINED window (what observability consumers want);
    :meth:`since` is the billing protocol."""

    def __init__(self, maxlen: int = 4096):
        self.maxlen = maxlen
        self.offset = 0                      # absolute index of _items[0]
        self._items: List[Tuple[str, float]] = []

    def append(self, item):
        self._items.append(item)
        if len(self._items) > self.maxlen:
            drop = len(self._items) - self.maxlen
            del self._items[:drop]
            self.offset += drop

    def since(self, cursor: int) -> Tuple[List[Tuple[str, float]], int]:
        """Entries at absolute index >= ``cursor`` (clamped to the retained
        window) and the new cursor. Entries already trimmed are gone — the
        ring must be sized above the billing cadence."""
        start = max(int(cursor), self.offset)
        return self._items[start - self.offset:], self.offset + len(self._items)

    def total(self) -> int:
        return self.offset + len(self._items)

    def __len__(self) -> int:
        return len(self._items)

    def __iter__(self):
        return iter(self._items)

    def __getitem__(self, i):
        return self._items[i]


def _value_readout(logits):
    """Critic value estimate per token without a dedicated value head: the
    free-energy (logsumexp) of the logits, squashed to (-1, 1). It is
    differentiable w.r.t. the whole backbone, so the clipped value loss
    trains a role="critic" deployment through the same FORWARD_BACKWARD /
    OPTIM_STEP primitives as the actor."""
    v = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.tanh(v / jnp.sqrt(logits.shape[-1] * 1.0))


def jit_update_actor(model: Model, grpo_cfg: grpo.GRPOConfig,
                     adamw_cfg: opt.AdamWConfig):
    """The UPDATE_ACTOR primitive as one compiled program:
    ``(params, opt_state, batch) -> (TrainState, metrics)``.

    The optimizer moments are the bulk of the train state (f32, twice the
    bf16 params) and only their own deployment reads them, so they are
    donated: the new moments reuse their buffers and an update holds one
    copy of them on the device, not two. Params are not donated, since a
    weight sync may share their arrays with another deployment."""
    step = grpo.make_update_actor(model, grpo_cfg, adamw_cfg)

    def _update(params, opt_state, batch):
        return step(TrainState(params, opt_state, jnp.asarray(0, jnp.int32)),
                    batch)

    return jax.jit(_update, donate_argnums=1)


class WorkerProcessGroup:
    def __init__(self, spec: api.DeploymentSpec, state_manager: StateManager,
                 rng_seed: int = 0, grpo_cfg: Optional[grpo.GRPOConfig] = None,
                 adamw_cfg: Optional[opt.AdamWConfig] = None):
        self.spec = spec
        self.sm = state_manager
        cfg = get_config(spec.model_name)
        if spec.overrides:
            cfg = cfg.replace(**dict(spec.overrides))
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.grpo_cfg = grpo_cfg or grpo.GRPOConfig()
        self.ppo_cfg = ppo_lib.PPOConfig()
        self.adamw_cfg = adamw_cfg or opt.AdamWConfig()
        self._rng = jax.random.PRNGKey(rng_seed)
        self._initialized = False
        self._keys: Dict[str, list] = {}
        self.exec_log = ExecLog()
        # per-WPG state shardings, cached per mesh slice (rebuilt after a
        # cross-slice migration swaps self.sm)
        self._shard_cache: Optional[tuple] = None
        # jitted primitives (built lazily)
        self._update_actor = None
        self._logprob = None
        self._values = None
        self._ppo_grads = None
        self._value_grads = None

    # -------------------------------------------------------------- state
    @property
    def job_prefix(self) -> str:
        return f"{self.spec.job_id}:{self.spec.deployment_id}"

    # ---------------------------------------------------------- mesh slice
    @property
    def mesh_slice(self):
        """The node group's MeshSlice, read off the group's StateManager so
        a migration that swaps ``self.sm`` rebinds the WPG to the new
        group's hardware automatically."""
        return getattr(self.sm, "mesh_slice", None)

    def state_shardings(self) -> Optional[TrainState]:
        """NamedShardings for (params, opt_state, step) on THIS group's
        mesh slice — per-WPG, not global. None without a slice (legacy
        single-view execution). Cached per slice; jit re-specializes on
        sharding change, so no explicit invalidation is needed."""
        sl = self.mesh_slice
        if sl is None:
            return None
        if self._shard_cache is None or self._shard_cache[0] is not sl.mesh:
            self._shard_cache = (sl.mesh, tstate.shardings(
                self.model, sl.mesh, shd.named_rules("tp")))
        return self._shard_cache[1]

    def param_shardings(self):
        st = self.state_shardings()
        return None if st is None else st.params

    def _params_template(self):
        return self.model.abstract_params()

    def params(self):
        return self.sm.gather(self.job_prefix, self._params_template(),
                              "params")

    def opt_state(self) -> opt.AdamWState:
        tmpl = opt.abstract_state(self._params_template(), self.adamw_cfg)
        return self.sm.gather(self.job_prefix, tmpl, "opt")

    def _store(self, params=None, opt_state=None):
        if params is not None:
            self._keys["params"] = self.sm.reregister(
                self.job_prefix, params, Tier.DEVICE, "params")
        if opt_state is not None:
            self._keys["opt"] = self.sm.reregister(
                self.job_prefix, opt_state, Tier.DEVICE, "opt")

    def resident(self) -> bool:
        keys = self.sm.keys_for(self.job_prefix)
        return bool(keys) and all(
            self.sm.entries[k].tier == Tier.DEVICE for k in keys)

    def ensure_resident(self) -> float:
        """Load this WPG's state to device (the 'load' half of a context
        switch). Returns elapsed seconds."""
        keys = self.sm.keys_for(self.job_prefix)
        return self.sm.prefetch(keys)

    def offload(self, to: Tier = Tier.HOST) -> float:
        return self.sm.offload(self.sm.keys_for(self.job_prefix), to)

    # --------------------------------------------------------------- ops
    def execute(self, qop: api.QueuedOperation):
        """Serial execution of one admitted operation."""
        t0 = time.monotonic()
        handler = {
            api.Op.INIT: self._op_init,
            api.Op.GENERATE: self._op_generate,
            api.Op.FORWARD: self._op_forward,
            api.Op.FORWARD_BACKWARD: self._op_forward_backward,
            api.Op.OPTIM_STEP: self._op_optim_step,
            api.Op.UPDATE_ACTOR: self._op_update_actor,
            api.Op.SYNC_WEIGHTS: self._op_sync_weights,
            api.Op.SAVE_CHECKPOINT: self._op_save_checkpoint,
            api.Op.LOAD_CHECKPOINT: self._op_load_checkpoint,
        }[qop.op]
        result = handler(*qop.args, **qop.kwargs)
        self.exec_log.append((qop.op.value, time.monotonic() - t0))
        return result

    # ------------------------------------------------------ op handlers
    def _op_init(self, seed: int = 0):
        params = self.model.init_params(jax.random.PRNGKey(seed))
        st = self.state_shardings()
        if st is not None:
            # lay the state out on this group's mesh slice (per-WPG
            # shardings); the StateManager records each leaf's spec so
            # later prefetch/migrate rebuilds the layout
            params = jax.device_put(params, st.params)
        if self.spec.role in ("train", "critic"):
            # critic deployments run their own optim_step (value updates)
            opt_state = opt.init(params, self.adamw_cfg)
            if st is not None:
                opt_state = jax.device_put(opt_state, st.opt_state)
            self._store(params=params, opt_state=opt_state)
        else:
            self._store(params=params)
        self._initialized = True
        return {"params": self.model.param_count()}

    def _op_generate(self, prompt_tokens, max_new_tokens: int = 32,
                     temperature: float = 1.0, extra_inputs=None):
        params = self.params()
        self._rng, k = jax.random.split(self._rng)
        toks, logps, alive = rollout_lib.rollout(
            self.model, params, jnp.asarray(prompt_tokens), k,
            rollout_lib.RolloutConfig(max_new_tokens=max_new_tokens,
                                      temperature=temperature),
            extra_inputs=extra_inputs, counters=self.sm.telemetry)
        return {"tokens": toks, "logprobs": logps, "alive": alive}

    def _op_forward(self, batch, output: str = "logprobs"):
        """Forward-only primitive. ``output`` selects the readout:
        "logprobs" (compute_log_prob, default) or "values" (critic value
        estimates per token)."""
        if output == "values":
            if self._values is None:
                def _vals(p, b):
                    logits, _ = self.model.forward(p, b, None)[:2]
                    return _value_readout(logits)
                self._values = jax.jit(_vals)
            return self._values(self.params(), batch)
        if output != "logprobs":
            raise ValueError(f"unknown forward output {output!r}")
        if self._logprob is None:
            self._logprob = jax.jit(grpo.make_compute_log_prob(self.model))
        return self._logprob(self.params(), batch)

    def _op_forward_backward(self, batch, objective: str = "grpo"):
        """Split-phase gradient computation. ``objective`` selects the loss
        family: "grpo" (default), "ppo" (rl/ppo.py's clipped surrogate), or
        "value" (the clipped critic loss for role="critic" deployments), so
        multi-algorithm / multi-role jobs share one WPG primitive."""
        params = self.params()
        if objective == "value":
            if self._value_grads is None:
                def _vgrads(p, b):
                    def _loss(pp):
                        logits, aux = self.model.forward(pp, b, None)[:2]
                        values = _value_readout(logits)
                        vl = ppo_lib.value_loss(
                            values, b["value_targets"], b["old_values"],
                            b["loss_mask"], self.ppo_cfg)
                        return vl + 0.01 * aux, vl
                    return jax.value_and_grad(_loss, has_aux=True)(p)
                self._value_grads = jax.jit(_vgrads)
            (loss, vl), grads = self._value_grads(params, batch)
            return {"grads": grads,
                    "metrics": {"value_loss": vl, "loss": loss}}
        if objective == "ppo":
            if self._ppo_grads is None:
                def _grads(p, b):
                    return jax.value_and_grad(ppo_lib.ppo_loss, has_aux=True)(
                        p, self.model, b, self.ppo_cfg, None)
                self._ppo_grads = jax.jit(_grads)
            (loss, metrics), grads = self._ppo_grads(params, batch)
            return {"grads": grads, "metrics": dict(metrics, loss=loss)}
        if objective != "grpo":
            raise ValueError(f"unknown objective {objective!r}")
        grads, metrics = grpo.compute_grads(params, self.model, batch,
                                            self.grpo_cfg, None)
        return {"grads": grads, "metrics": metrics}

    def _op_optim_step(self, grads, host: bool = False):
        if host:
            # §4.5.4: CPU optimizer over host-resident canonical state
            step = self.sm.host_optimizer_step(
                self.job_prefix, grads, self._params_template(),
                lr=self.adamw_cfg.lr, b1=self.adamw_cfg.b1,
                b2=self.adamw_cfg.b2, eps=self.adamw_cfg.eps)
            return {"step": step, "host": True}
        params = self.params()
        state = self.opt_state()
        new_params, new_state, metrics = opt.update(grads, state, params,
                                                    self.adamw_cfg)
        self._store(params=new_params, opt_state=new_state)
        return {"step": int(new_state.step), **{k: float(v) for k, v in
                                                metrics.items()}}

    def _op_update_actor(self, batch):
        if self._update_actor is None:
            self._update_actor = jit_update_actor(self.model, self.grpo_cfg,
                                                  self.adamw_cfg)
        with telemetry.span("update.launch", job=self.spec.job_id):
            new_state, metrics = self._update_actor(self.params(),
                                                    self.opt_state(), batch)
            self._store(params=new_state.params,
                        opt_state=new_state.opt_state)
        with telemetry.span("update.sync", job=self.spec.job_id):
            return {k: float(v) for k, v in metrics.items()}

    def _op_sync_weights(self, target_wpg: "WorkerProcessGroup",
                         target_shardings=None):
        """Materialise training-visible weights into the rollout deployment's
        layout (zero-redundancy resharding via StateManager). By default the
        target layout is the TARGET WPG's own per-group shardings — a
        rollout deployment on a different mesh slice receives the weights
        resharded onto ITS slice, not this group's."""
        if target_shardings is None and hasattr(target_wpg, "param_shardings"):
            target_shardings = target_wpg.param_shardings()
        tree = self.sm.sync_weights(self.job_prefix, self._params_template(),
                                    target_shardings)
        target_wpg._store(params=tree)
        return {"synced_bytes": self.sm.job_bytes(self.job_prefix)}

    def _op_save_checkpoint(self, path: str, step: int = 0):
        return self.sm.materialize_checkpoint(
            self.job_prefix, self._params_template(), path, step)

    def _op_load_checkpoint(self, path: str):
        from repro.train import checkpoint as ckpt
        tree, meta = ckpt.restore(path, self._params_template())
        self._store(params=tree)
        return meta
