"""Batched serving driver: prefill + decode loop through the service API.

Demonstrates the rollout side of PlexRL as a standalone deployment on a
LIVE serve-mode plane: the Router's dispatch worker parks while idle,
admits each batched generate the moment it is submitted, and the client
simply blocks on the returned future — the request/response shape of a
real inference service, through the same dataflow client API the RL
controllers use.

    PYTHONPATH=src python -m repro.launch.serve --batch 8 --max-new 32

As in ``launch.train``, ``--layers 0 --d-model 0 --vocab 0`` keeps the
architecture's published widths; the defaults are a small CPU-sized cut.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core.router import Router
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import size_overrides
from repro.rl import data as data_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args(argv)

    use_compile_cache()
    router = Router()
    spec = api.DeploymentSpec(
        deployment_id="serve", job_id="serve", model_name=args.arch,
        role="rollout", overrides=size_overrides(args))
    dep = router.deploy(spec, group_id=0)

    ds = data_lib.MathDataset(seed=0)
    batches = ds.batches(args.batch, args.prompt_len)
    lat = []
    with router:                      # persistent plane: serve()...shutdown()
        dep.init(seed=0).wait(timeout=600)
        for r in range(args.rounds):
            prompts, problems = next(batches)
            t0 = time.time()
            out = dep.generate(jnp.asarray(prompts),
                               max_new_tokens=args.max_new,
                               temperature=0.7).wait(timeout=600)
            dt = time.time() - t0
            lat.append(dt)
            toks = int(np.asarray(out["alive"]).sum())
            print(f"round {r}: {dt*1000:.0f} ms, {toks} live tokens, "
                  f"{toks / dt:.1f} tok/s, sample: "
                  f"{data_lib.decode(np.asarray(out['tokens'][0]))!r}")
    print(f"mean latency {np.mean(lat)*1000:.0f} ms "
          f"(first includes jit compile)")


if __name__ == "__main__":
    main()
