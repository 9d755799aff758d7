"""Compiles for a described TPU v5e chip (no chip attached): the three
Pallas kernels at the widths of the configurations they serve, and the
full-width qwen2-0.5b UPDATE_ACTOR step with its device-memory bound,
mamba2-2.7b's UPDATE_ACTOR and GENERATE at the benchmark cell's cut (8
layers, 16 rows of 256 positions), and the state plane's offload copy into
reused pinned host buffers.

What this guards that interpret mode cannot: block shapes the TPU compiler
tiles, Mosaic lowering of the kernel bodies, and a train step that fits one
chip's HBM. The topology is described inside a module fixture, never at
import, and the persistent compile cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import state_manager as sm_lib
from repro.core.worker import jit_update_actor
from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ssd as ssd_k
from repro.launch.mesh import PRODUCTION_KIND, hw_peaks
from repro.models.registry import build_model
from repro.rl import grpo
from repro.rl import rollout as rollout_lib
from repro.train import optimizer as opt


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_at_qwen2_width(sds):
    cfg = get_config("qwen2-0.5b")
    qkv = sds((2, cfg.num_heads, 1024, cfg.resolved_head_dim))
    compiled = _compile(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, causal=True),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_at_qwen2_width(sds):
    cfg = get_config("qwen2-0.5b")
    d = cfg.resolved_head_dim
    cache = sds((8, cfg.num_kv_heads, 2048, d))
    compiled = _compile(
        lambda q, k, v, pos: dec.decode_attention(q, k, v, pos),
        sds((8, cfg.num_heads, d)), cache, cache, sds((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_compiles_at_mamba2_width(sds):
    cfg = get_config("mamba2-2.7b")
    h, p, n, s = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state, 1024
    f32 = jnp.float32
    compiled = _compile(
        lambda x, dt, a, b, c: ssd_k.ssd_chunk_scan(x, dt, a, b, c,
                                                    chunk=cfg.ssm_chunk),
        sds((1, h, s, p)), sds((1, h, 1, s), f32), sds((h,), f32),
        sds((1, s, n), f32), sds((1, s, n), f32))
    assert "tpu_custom_call" in compiled.as_text()


def _on_chip(sds, tree):
    return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)


def _fits_one_chip(mem) -> int:
    """Arguments + outputs + temporaries of a compiled program, asserted
    under one chip's HBM."""
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < hw_peaks(PRODUCTION_KIND)["hbm_bytes"], total
    return total


def _update_actor_memory(sds, model, b, s):
    """The served UPDATE_ACTOR step's memory analysis at B=b, S=s; the
    donated moments alias their outputs."""
    adamw = opt.AdamWConfig()
    params = _on_chip(sds, model.abstract_params())
    opt_state = _on_chip(sds, opt.abstract_state(model.abstract_params(),
                                                  adamw))
    batch = {"tokens": sds((b, s), jnp.int32),
             "behavior_logprobs": sds((b, s), jnp.float32),
             "advantages": sds((b,), jnp.float32),
             "loss_mask": sds((b, s), jnp.float32)}
    step = jit_update_actor(model, grpo.GRPOConfig(), adamw)
    mem = step.lower(params, opt_state, batch).compile().memory_analysis()
    _fits_one_chip(mem)
    moments = 2 * sum(x.size * 4 for x in
                      jax.tree.leaves(model.abstract_params()))
    assert mem.alias_size_in_bytes >= moments, (mem.alias_size_in_bytes,
                                                moments)
    return mem


def test_qwen2_update_actor_fits_one_chip(sds):
    """The served UPDATE_ACTOR step at published widths (B=16, S=64):
    arguments + outputs + temporaries stay under one chip's HBM."""
    _update_actor_memory(sds, build_model(get_config("qwen2-0.5b")), 16, 64)


def _mamba2_cell_model():
    """mamba2-2.7b as the benchmark cell runs it: 8 of its 64 layers."""
    return build_model(get_config("mamba2-2.7b").replace(num_layers=8))


def test_mamba2_update_actor_fits_one_chip(sds):
    """mamba2-2.7b's served UPDATE_ACTOR at the cell's cut (B=16, S=256,
    one SSD chunk of 256): 4.50 GB of train state, the new params and the
    chunked scan's temporaries fit one chip."""
    mem = _update_actor_memory(sds, _mamba2_cell_model(), 16, 256)
    assert mem.argument_size_in_bytes > 4.5e9


def test_mamba2_generate_fits_one_chip(sds):
    """GENERATE at the cell's sizes (16 rows, 64 prompt + 192 new tokens)
    as one program: the prefill and the decode loop, which carries each
    row's conv window and f32 SSM state through its 192 steps."""
    model = _mamba2_cell_model()
    cfg = rollout_lib.RolloutConfig(max_new_tokens=192)
    gen = jax.jit(lambda p, prompts, key: rollout_lib.rollout(
        model, p, prompts, key, cfg))
    mem = gen.lower(_on_chip(sds, model.abstract_params()),
                    sds((16, 64), jnp.int32),
                    sds((2,), jnp.uint32)).compile().memory_analysis()
    _fits_one_chip(mem)
    state = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(model.abstract_cache(16, 256)))
    assert state > 3.3e8                        # 8 x 16 x 80 x 64 x 128 x 4 B
    assert mem.temp_size_in_bytes >= state


def test_offload_copies_land_in_the_donated_pinned_buffers(topo, sds):
    """The offload's one program (``state_manager._copy_into``) over a
    job's stacked leaves: every output is in pinned host memory, and the
    outputs of the leaves given a buffer alias it (no allocation)."""
    pinned = SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")
    leaves = [sds((24, 896, 4864), jnp.float32), sds((24, 896)),
              sds((151936, 896))]
    slots = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=pinned)
             for x in leaves]
    slots[1] = None                     # a leaf with no buffer yet
    compiled = sm_lib._copy_into.lower(slots, leaves).compile()
    assert all(s.memory_kind == "pinned_host"
               for s in compiled.output_shardings)
    mem = compiled.memory_analysis()
    given = sum(x.size * x.dtype.itemsize for x in slots if x is not None)
    assert mem.host_alias_size_in_bytes == given
    assert mem.host_output_size_in_bytes == given + 24 * 896 * 2
