import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

DOC = """Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

For each cell this produces (into benchmarks/results/dryrun_<...>.json):
  * memory_analysis()  — per-device argument/output/temp bytes (fits check)
  * cost_analysis()    — per-device HLO flops + bytes accessed
  * collective bytes   — parsed from the post-SPMD compiled HLO text: the sum
    of per-device shard sizes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute ops (all-reduce counted 2x: RS+AG)
  * the three roofline terms (seconds) per EXPERIMENTS.md §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--all] [--out benchmarks/results]
"""
__doc__ = DOC

import argparse
import dataclasses
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import SHAPES, ModelConfig, RunConfig, ShapeConfig
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.parallel import rules

# v5e hardware constants (per chip) — roofline denominators.
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s/link ICI

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(sig: str) -> int:
    """Sum byte sizes of every dtype[shape] in an HLO type signature."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved by each collective kind (post-SPMD shapes)."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # "%name = TYPE op-name(...)" — take the output type signature.
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([a-z\-]+)\(", s)
        if not m:
            continue
        sig, op = m.groups()
        if op.rstrip("-start") in _COLLECTIVES or op in (
                c + "-start" for c in _COLLECTIVES):
            kind = op.replace("-start", "")
            if kind not in out:
                continue
            nbytes = _shape_bytes(sig)
            if kind == "all-reduce":
                nbytes *= 2          # ring all-reduce = reduce-scatter + all-gather
            out[kind] += nbytes
    return out


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, rc: RunConfig, ocfg: opt.OptimizerConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, rc, p, batch))(params)
        params, opt_state, metrics = opt.apply_updates(ocfg, params, grads,
                                                       opt_state)
        return params, opt_state, {"loss": loss, **metrics}
    return train_step


def build_prefill_step(cfg: ModelConfig, rc: RunConfig, cache_len: int):
    def prefill_step(params, batch):
        return M.prefill(cfg, rc, params, batch, cache_len)
    return prefill_step


def build_serve_step(cfg: ModelConfig, rc: RunConfig):
    def serve_step(params, cache, batch):
        return M.decode_step(cfg, rc, params, cache, batch)
    return serve_step


def _named(mesh, spec_tree, shape_tree):
    return jax.tree.map(
        lambda spec, sds: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


def abstract_train_inputs(cfg, rc, ocfg, mesh):
    pshapes = M.abstract_params(cfg)
    pspecs = M.param_specs(cfg, mesh, rc.seq_parallel)
    params = _named(mesh, pspecs, pshapes)
    oshapes = jax.eval_shape(functools.partial(opt.init_state, ocfg), pshapes)
    ospecs = {"step": P()}
    for k in oshapes:
        if k != "step":
            ospecs[k] = pspecs
    opt_state = _named(mesh, ospecs, oshapes)
    return params, opt_state, pspecs, ospecs


# ---------------------------------------------------------------------------
# the cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rc: RunConfig | None = None, verbose: bool = True,
             save_hlo: str | None = None) -> dict:
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = registry.applicable(cfg, shape)
    cell = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        cell.update(status="skipped", reason=reason)
        return cell
    rc = rc or default_rc(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    ocfg = opt.OptimizerConfig()
    t0 = time.time()
    with rules.use_rules_mesh(mesh, rc.seq_parallel):
        inputs = registry.input_specs(cfg, shape, mesh, rc)
        if shape.kind == "train":
            params, opt_state, pspecs, ospecs = abstract_train_inputs(
                cfg, rc, ocfg, mesh)
            fn = build_train_step(cfg, rc, ocfg)
            jfn = jax.jit(fn, donate_argnums=(0, 1))
            args = (params, opt_state, inputs)
        elif shape.kind == "prefill":
            pshapes = M.abstract_params(cfg)
            pspecs = M.param_specs(cfg, mesh, rc.seq_parallel)
            params = _named(mesh, pspecs, pshapes)
            fn = build_prefill_step(cfg, rc, shape.seq_len)
            jfn = jax.jit(fn)
            args = (params, inputs)
        else:  # decode
            pshapes = M.abstract_params(cfg)
            pspecs = M.param_specs(cfg, mesh, rc.seq_parallel)
            params = _named(mesh, pspecs, pshapes)
            cache = inputs.pop("cache")
            fn = build_serve_step(cfg, rc)
            jfn = jax.jit(fn, donate_argnums=(1,))
            args = (params, cache, inputs)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if save_hlo:
        import gzip
        with gzip.open(save_hlo, "wt") as f:
            f.write(compiled.as_text())
    t0 = time.time()
    hlo = hlo_analysis.analyze(compiled.as_text())
    t_analyze = time.time() - t0
    flops = float(hlo["flops"])              # trip-count-aware, per device
    bytes_acc = float(hlo["bytes"])
    coll = {k: float(v) for k, v in hlo["collectives"].items()}
    coll_total = float(hlo["collective_total"])
    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll_total / LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    model_flops = model_flops_per_step(cfg, shape)
    cell.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        analyze_s=round(t_analyze, 2),
        memory={k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")},
        hlo_flops_per_device=flops,
        hlo_bytes_per_device=bytes_acc,
        xla_cost_flops_per_device=float(cost.get("flops", 0.0)),
        xla_cost_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=coll,
        collective_total_per_device=coll_total,
        roofline_terms_s=terms,
        dominant=dominant,
        model_flops_global=model_flops,
        useful_ratio=(model_flops / (flops * chips)) if flops else None,
        step_time_bound_s=max(terms.values()),
    )
    if verbose:
        print(json.dumps(cell, indent=2))
    return cell


def model_flops_per_step(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS: 6*N*D (dense) / 6*N_active*D (MoE) per optimizer step;
    for prefill 2*N*D (fwd only); decode: per generated token."""
    n = active_param_count(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n * tokens


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top-k experts only)."""
    total = cfg.param_count()
    if cfg.num_experts:
        e, k = cfg.num_experts, cfg.experts_per_token
        expert_params = sum(
            count * e * (3 if cfg.act == "silu" else 2)
            * cfg.d_model * cfg.moe_d_ff
            for kind, count in cfg.block_pattern if kind == "moe")
        total = total - expert_params + expert_params * k // e
    return total


def default_rc(cfg: ModelConfig, shape: ShapeConfig) -> RunConfig:
    rc = RunConfig(seq_len=shape.seq_len, global_batch=shape.global_batch)
    if shape.seq_len >= 32768 and shape.kind != "decode":
        rc = dataclasses.replace(rc, q_block=1024, kv_block=1024)
    return rc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) for the chosen mesh")
    ap.add_argument("--out", default="benchmarks/results")
    ap.add_argument("--save-hlo", action="store_true",
                    help="save gzipped compiled HLO text per cell")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    cells = ([(a, s) for a in registry.ARCHS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    results = []
    for arch, shape in cells:
        tag = f"{arch}__{shape}__{mesh_tag}"
        print(f"=== {tag} ===", flush=True)
        try:
            hlo_path = (os.path.join(args.out, f"hlo_{tag}.txt.gz")
                        if args.save_hlo else None)
            cell = run_cell(arch, shape, args.multi_pod, save_hlo=hlo_path)
        except Exception as e:
            cell = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]}
            print(cell["error"], flush=True)
        results.append(cell)
        with open(os.path.join(args.out, f"dryrun_{tag}.json"), "w") as f:
            json.dump(cell, f, indent=2)
    n_ok = sum(c["status"] == "ok" for c in results)
    n_skip = sum(c["status"] == "skipped" for c in results)
    n_err = len(results) - n_ok - n_skip
    print(f"\nDRYRUN SUMMARY [{mesh_tag}]: ok={n_ok} skipped={n_skip} "
          f"errors={n_err}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
