import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes and extract roofline terms from the compiled
artifact. MUST be run as its own process (the device-count flag above is
locked in at first jax init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

Artifacts: benchmarks/artifacts/dryrun/<mesh>/<arch>__<shape>.json with
memory analysis, HLO flops/bytes, per-collective byte totals, and the
collective op schedule — consumed by benchmarks.roofline and EXPERIMENTS.md.
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, ShapeSpec, get_config, shape_applicable
from repro.launch import hlo_cost
from repro.launch.mesh import PRODUCTION_KIND, hw_peaks, make_production_mesh
from repro.models import sharding as shd
from repro.models.layers import Ctx
from repro.models.registry import build_model
from repro.rl import grpo
from repro.train import optimizer as opt, train_state as ts

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

# Models whose optimizer state cannot fit on a single pod at f32 moments:
# the paper's answer is ZeRO-offload (host-resident optimizer, §6.2), so
# their train cells lower the grad-step (fwd+bwd+reduce-scatter) and the
# optimizer update runs host-side via the StateManager (§4.5.4).
HOST_OPTIM = {"arctic-480b", "paper-qwen3-235b-a22b"}

# Sharding mode per arch: small models keep the paper-faithful ZeRO-2 layout
# (params TP-only, replicated over data); large models need FSDP+TP to fit
# (analogue of the paper's heavy PP/TP splits in Tab. 1).
def default_rules_name(arch: str, shape: ShapeSpec) -> str:
    if shape.name == "long_500k":
        return "long"
    cfg = get_config(arch)
    from repro.models.registry import build_model as _bm
    big = _bm(cfg).param_count() * 2 > 8e9  # >8 GB of bf16 params
    # MoE always gets FSDP: the dispatch buffers need the embed/data shard
    return "fsdp_tp" if (big or cfg.num_experts) else "tp"


def default_grad_accum(arch: str, shape: ShapeSpec, mesh) -> int:
    """Pick the microbatch count so per-device saved activations stay ~<6GB."""
    if shape.kind != "train":
        return 1
    cfg = get_config(arch)
    data_shards = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            data_shards *= mesh.shape[ax]
    b_local = max(1, shape.global_batch // data_shards)
    layers = cfg.num_layers + cfg.encoder_layers
    carry_bytes = layers * b_local * shape.seq_len * cfg.d_model * 2 * 2.5
    accum = 1
    while carry_bytes / accum > 6e9 and accum < b_local:
        accum *= 2
    return accum


def _collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum result-shape bytes of every collective op in the compiled HLO."""
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    widths = {"f64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
              "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
              "u64": 8, "c64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    totals = {k: 0 for k in kinds}
    counts = {k: 0 for k in kinds}
    ops = []
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped.startswith("%") and " = " not in stripped:
            continue
        for kind in kinds:
            # match the op use, not substrings of other ops
            marker = f" {kind}("
            alt = f" {kind}-start("
            idx = stripped.find(marker)
            if idx < 0:
                idx = stripped.find(alt)
            if idx < 0:
                continue
            lhs = stripped[:idx]
            if "=" not in lhs:
                continue
            result = lhs.split("=", 1)[1]
            nbytes = 0
            for dt, dims in shape_re.findall(result):
                if dt not in widths:
                    continue
                n = 1
                if dims:
                    for d in dims.split(","):
                        n *= int(d)
                nbytes += n * widths[dt]
            totals[kind] += nbytes
            counts[kind] += 1
            ops.append({"kind": kind, "bytes": nbytes})
            break
    return {"bytes_by_kind": totals, "counts": counts,
            "total_bytes": sum(totals.values()), "ops": ops[:400]}


def _memory_stats(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _cost_stats(compiled) -> Dict[str, Any]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def build_cell(arch: str, shape: ShapeSpec, mesh, rules_name: str,
               host_optim: Optional[bool] = None,
               grad_accum: Optional[int] = None,
               overrides: Optional[dict] = None):
    """Returns (fn, args_abstract, in_shardings, out_shardings, donate)."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    rules = shd.named_rules(rules_name)
    ctx = Ctx(mesh, rules)
    if host_optim is None:
        host_optim = arch in HOST_OPTIM
    if grad_accum is None:
        grad_accum = default_grad_accum(arch, shape, mesh)

    batch_specs = model.input_specs(shape)
    batch_abs = {k: v.sds for k, v in batch_specs.items()}
    batch_shd = {
        k: NamedSharding(mesh, shd.resolve(v.axes, mesh, rules, v.sds.shape))
        for k, v in batch_specs.items()
    }
    param_shd = shd.tree_shardings(model.logical_axes(), mesh, rules,
                                   model.abstract_params())

    if shape.kind == "train":
        if host_optim:
            # ZeRO-offload: lower fwd+bwd; grads reduce-scattered over data
            def grad_step(params, batch):
                return grpo.compute_grads(params, model, batch,
                                          grpo.GRPOConfig(), ctx, grad_accum)

            ap = model.abstract_params()
            pspecs = shd.tree_partition_specs(model.logical_axes(), mesh,
                                              rules, ap)
            gspecs = jax.tree.map(
                lambda ps, a: opt.zero_moment_spec(ps, a.shape, mesh),
                pspecs, ap, is_leaf=lambda x: isinstance(x, P))
            gshd = jax.tree.map(lambda p: NamedSharding(mesh, p), gspecs,
                                is_leaf=lambda x: isinstance(x, P))
            out_shd = (gshd, None)
            return (grad_step, (model.abstract_params(), batch_abs),
                    (param_shd, batch_shd), out_shd, (0,))

        step = grpo.make_update_actor(model, ctx=ctx, grad_accum=grad_accum)
        state_abs = ts.abstract(model)
        state_shd = ts.shardings(model, mesh, rules, zero=True)
        return (step, (state_abs, batch_abs), (state_shd, batch_shd),
                (state_shd, None), (0,))

    if shape.kind == "prefill":
        step = grpo.make_prefill(model, ctx=ctx)
        cache_abs = model.abstract_cache(shape.global_batch, shape.seq_len)
        from repro.models import common
        cache_axes = common.logical_axes(
            model.cache_specs(shape.global_batch, shape.seq_len))
        # prefill OUTPUTS the cache seq-sharded (cheap per-layer slicing of
        # the K/V stack): forcing the decode layout (cache_hd fallback) here
        # makes GSPMD reshard inside the scan — the prefill->decode reshard
        # belongs between the two calls, paid once
        def _prefill_ax(ax):
            if ax == "cache_hd":
                return None
            if ax == "cache_seq":
                return "cache_seq_out"
            return ax
        cache_axes = jax.tree.map(
            lambda a: tuple(_prefill_ax(ax) for ax in a), cache_axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                v is None or isinstance(v, str) for v in x))
        cache_shd = shd.tree_shardings(cache_axes, mesh, rules, cache_abs)
        logits_shape = (shape.global_batch, 1, cfg.vocab_size)
        logits_shd = NamedSharding(
            mesh, shd.resolve(("batch", None, "vocab"), mesh, rules,
                              shape=logits_shape))
        return (step, (model.abstract_params(), batch_abs),
                (param_shd, batch_shd), (logits_shd, cache_shd), ())

    # decode
    step = grpo.make_decode(model, ctx=ctx)
    from repro.models import common
    cache_specs = model.cache_specs(shape.global_batch, shape.seq_len)
    cache_abs = common.abstract_params(cache_specs)
    cache_axes = common.logical_axes(cache_specs)
    cache_shd = shd.tree_shardings(cache_axes, mesh, rules, cache_abs)
    logits_shape = (shape.global_batch, 1, cfg.vocab_size)
    logits_shd = NamedSharding(
        mesh, shd.resolve(("cache_batch", None, "vocab"), mesh, rules,
                          shape=logits_shape))
    return (step, (model.abstract_params(), cache_abs, batch_abs),
            (param_shd, cache_shd, batch_shd), (logits_shd, cache_shd), (1,))


def pad_heads_overrides(arch: str, mesh_model: int = 16) -> dict:
    """Perf variant: pad query heads up to a mesh multiple so attention
    shards over the model axis (extra heads are wasted compute — 14 % for
    deepseek's 56->64 — but beat 16x replication). Semantically the padded
    wq/wo rows would be zero-initialised."""
    cfg = get_config(arch)
    h = cfg.num_heads
    padded = -(-h // mesh_model) * mesh_model
    return {"num_heads": padded} if padded != h else {}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_name: Optional[str] = None,
             host_optim: Optional[bool] = None,
             verbose: bool = True,
             overrides: Optional[dict] = None) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind,
    }
    if not ok:
        result["status"] = "SKIP"
        result["reason"] = reason
        return result
    rules_name = rules_name or default_rules_name(arch, shape)
    result["rules"] = rules_name
    mesh = make_production_mesh(multi_pod=multi_pod)
    result["grad_accum"] = default_grad_accum(arch, shape, mesh)
    result["host_optim"] = arch in HOST_OPTIM and shape.kind == "train"
    n_chips = mesh.size
    t0 = time.time()
    fn, args_abs, in_shd, out_shd, donate = build_cell(
        arch, shape, mesh, rules_name, host_optim, overrides=overrides)
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_shd, out_shardings=out_shd,
                         donate_argnums=donate)
        lowered = jitted.lower(*args_abs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = _memory_stats(compiled)
    cost = _cost_stats(compiled)
    # trip-count-weighted HLO analysis (xla cost_analysis counts scan bodies
    # once — see repro.launch.hlo_cost)
    hc = hlo_cost.analyze(compiled.as_text())

    model = build_model(cfg)
    n_params = model.param_count()
    n_active = model.active_param_count()
    flops = hc["flops"]                      # per-device, trip-weighted
    hlo_flops_total = flops * n_chips
    bytes_acc = hc["traffic_bytes"]
    coll_bytes = hc["collective_bytes"]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = {"train": 6, "prefill": 2, "decode": 2}[shape.kind]
    model_flops = mult * n_active * tokens

    hw = hw_peaks(PRODUCTION_KIND)
    compute_s = flops / hw["peak_flops_bf16"]
    memory_s = bytes_acc / hw["hbm_bw"]
    collective_s = coll_bytes / hw["ici_bw"]
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]

    result.update({
        "status": "OK",
        "n_chips": n_chips,
        "params": n_params,
        "active_params": n_active,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "xla_cost_analysis": cost,
        "collectives": {
            "bytes_by_kind": {k: hc.get(f"bytes_{k}", 0.0)
                              for k in hlo_cost.COLLECTIVES},
            "counts": hc.get("collective_counts", {}),
            "total_bytes": coll_bytes,
        },
        "roofline": {
            "hlo_flops_per_device": flops,
            "hlo_bytes_per_device": bytes_acc,
            "collective_bytes_per_device": coll_bytes,
            "compute_term_s": compute_s,
            "memory_term_s": memory_s,
            "collective_term_s": collective_s,
            "dominant": dominant,
            "model_flops_total": model_flops,
            "hlo_flops_total": hlo_flops_total,
            "useful_flops_ratio": (model_flops / hlo_flops_total
                                   if hlo_flops_total else 0.0),
        },
    })
    if verbose:
        per_dev = (mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
                   + mem.get("output_bytes", 0) - mem.get("alias_bytes", 0))
        print(f"[{mesh_name}] {arch} x {shape_name} ({rules_name}): "
              f"compile {t_compile:.1f}s | "
              f"mem/dev {per_dev/1e9:.2f} GB | "
              f"flops/dev {flops:.3e} | coll {coll_bytes/1e9:.3f} GB "
              f"| dominant={dominant} | useful={100*result['roofline']['useful_flops_ratio']:.1f}%")
        print("  memory_analysis:", {k: f"{v/1e9:.3f}GB" for k, v in mem.items()
                                     if isinstance(v, int)})
        ck = {k: f"{v/1e6:.1f}MB"
              for k, v in result["collectives"]["bytes_by_kind"].items() if v}
        print("  collectives:", ck or "none")
    return result


def artifact_path(arch: str, shape_name: str, multi_pod: bool) -> str:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    d = os.path.abspath(os.path.join(ART_DIR, mesh_name))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rules", default=None, choices=[None, "tp", "fsdp_tp", "long"])
    ap.add_argument("--force", action="store_true", help="ignore cached artifacts")
    args = ap.parse_args(argv)

    if args.all:
        archs = list(ARCH_IDS)
        shapes = list(SHAPES)
    else:
        archs = [args.arch]
        shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                path = artifact_path(arch, shape_name, multi_pod)
                if os.path.exists(path) and not args.force and args.rules is None:
                    print(f"cached: {path}")
                    continue
                try:
                    res = run_cell(arch, shape_name, multi_pod, args.rules)
                except Exception as e:
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": "multipod_2x16x16" if multi_pod else "pod_16x16",
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}"}
                    failures.append((arch, shape_name, multi_pod))
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
