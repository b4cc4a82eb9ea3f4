"""Multi-pod dry-run (assignment deliverable e).

Lowers + compiles every (architecture × shape) cell against the production
mesh — 16x16 single pod and 2x16x16 multi-pod — and extracts the roofline
terms from the compiled artifact:

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = modeled link-bytes (per collective op, ring formulas) / ICI_bw

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --out results/
"""

import argparse
import json
import os
import time
from functools import partial

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config
from ..distributed.context import use_mesh
from ..distributed.sharding import param_shardings
from ..models.lm import decode_step, forward
from ..train.optimizer import (AdamWConfig, adamw_init,
                               opt_state_shardings)
from ..train.step import make_train_step
from .hlo_cost import analyze
from .mesh import make_production_mesh
from .specs import (SHAPES, abstract_caches, abstract_params,
                    batch_specs, cache_shardings, cell_supported, sds_with)

# ---- TPU v5e hardware constants (assignment §ROOFLINE) ----
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link

def model_flops(cfg, shape_name: str) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (per step)."""
    seq, batch, kind = SHAPES[shape_name]
    _total, active = cfg.count_params()
    if kind == "train":
        tokens = seq * batch
        return 6.0 * active * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * active * tokens
    return 2.0 * active * batch  # decode: one token per sequence


def build_cell(cfg, shape_name: str, mesh, accum: int):
    """Returns (fn, arg_sds) for the cell's step function."""
    seq, batch, kind = SHAPES[shape_name]
    p_shapes = abstract_params(cfg)
    p_shard = param_shardings(p_shapes, mesh)
    p_sds = sds_with(p_shapes, p_shard)

    if kind == "train":
        opt_cfg = AdamWConfig(
            moments_dtype="bfloat16" if cfg.count_params()[0] > 2e11 else "float32")
        o_shapes = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), p_shapes)
        o_shard = opt_state_shardings(p_shapes, mesh, opt_cfg)
        o_sds = sds_with(o_shapes, o_shard)
        b_sds = batch_specs(cfg, mesh, seq, batch, with_labels=True)
        step = make_train_step(cfg, opt_cfg, accum=accum)
        return jax.jit(step, donate_argnums=(0, 1)), (p_sds, o_sds, b_sds)

    if kind == "prefill":
        b_sds = batch_specs(cfg, mesh, seq, batch, with_labels=False)

        def prefill(params, batch):
            logits, _aux, caches, _ = forward(cfg, params, batch,
                                              want_caches=True)
            return logits[:, -1:], caches

        return jax.jit(prefill), (p_sds, b_sds)

    # decode
    c_shapes = abstract_caches(cfg, batch, seq)
    c_shard = cache_shardings(c_shapes, mesh, batch)
    c_sds = sds_with(c_shapes, c_shard)
    tok = batch_specs(cfg, mesh, 1, batch, with_labels=False,
                      decode=True)["tokens"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = partial(decode_step, cfg)
    return jax.jit(step, donate_argnums=(1,)), (p_sds, c_sds, tok, pos)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict) -> dict:
    ok, why = cell_supported(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skip", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch).with_(
        decode_seq_shards=mesh.shape["model"],
        **{k: v for k, v in overrides.items() if k in
           ("attn_chunk", "remat", "moe_dispatch") and v is not None})
    accum = overrides.get("accum") or default_accum(arch)
    t0 = time.time()
    with use_mesh(mesh):
        fn, args = build_cell(cfg, shape_name, mesh, accum)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
    # trip-count-aware cost model (XLA's cost_analysis counts scan bodies
    # once; see hlo_cost.py) — all values are per device. The roofline terms
    # use TPU-dtype-corrected accounting (CPU legalizes bf16 to f32; those
    # buffers/collectives do not exist on the TPU target); raw CPU-HLO
    # numbers are kept alongside.
    cost = analyze(hlo, tpu_dtype_correction=True)
    cost_raw = analyze(hlo)
    flops_dev = cost.flops
    bytes_dev = cost.bytes
    coll_bytes_dev = cost.collective_bytes
    n_dev = mesh.size
    mf = model_flops(cfg, shape_name)
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_bytes_dev / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "accum": accum,
        "remat": cfg.remat,
        "attn_chunk": cfg.attn_chunk,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "devices": n_dev,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      + ma.output_size_in_bytes
                                      - ma.alias_size_in_bytes),
        },
        "flops_per_device": flops_dev,
        "hbm_bytes_per_device": bytes_dev,
        "raw_cpu_hlo": {"hbm_bytes": cost_raw.bytes,
                        "collective_bytes": cost_raw.collective_bytes},
        "collectives": {k: {"count": v[0], "link_bytes": v[1]}
                        for k, v in sorted(cost.coll.items())},
        "collective_bytes_per_device": coll_bytes_dev,
        "roofline": terms,
        "dominant": dominant,
        "model_flops_per_device": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops_dev if flops_dev else 0.0,
    }
    return result


def default_accum(arch: str) -> int:
    big = {"deepseek-67b": 8, "jamba-1.5-large-398b": 8,
           "llava-next-34b": 8, "starcoder2-15b": 8, "qwen3-8b": 4,
           "deepseek-v2-lite-16b": 4}
    return big.get(arch, 1)


def main() -> None:
    # 512 virtual CPU devices for the production meshes; set here, before the
    # first JAX backend use, so importing this module changes no environment
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--moe-dispatch", dest="moe_dispatch", default=None)
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    overrides = {"accum": args.accum, "attn_chunk": args.attn_chunk,
                 "remat": args.remat, "moe_dispatch": args.moe_dispatch}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                res = run_cell(arch, shape, mp, overrides)
                line = json.dumps(res)
                print(line, flush=True)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    mesh_tag = "2x16x16" if mp else "16x16"
                    fn = f"{args.out}/{arch}__{shape}__{mesh_tag}__{args.tag}.json"
                    with open(fn, "w") as f:
                        f.write(line)


if __name__ == "__main__":
    main()
