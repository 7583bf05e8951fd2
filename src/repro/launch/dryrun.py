import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each runnable cell (configs/shapes.py::cell_status) this script builds
ShapeDtypeStruct stand-ins for params / optimizer state / batch / cache,
jits the step with explicit in/out shardings, ``.lower().compile()``s it on
the production mesh (single-pod 16x16 and multi-pod 2x16x16 over 512
host-platform placeholder devices), prints memory_analysis / cost_analysis,
and records the three-term roofline (repro/roofline) to a JSONL file that
EXPERIMENTS.md §Dry-run / §Roofline read from.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out runs/dryrun.jsonl
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.shapes import SHAPES, cell_status
from ..distributed.sharding import param_specs
from ..models import registry
from ..models import transformer as T
from ..optim import adamw
from ..roofline import analysis
from ..serve import steps as serve_steps
from ..train.train_step import make_train_step
from .mesh import make_production_mesh, mesh_context

#: the production meshes are v5e pods (see launch/mesh.py).
TARGET_DEVICE_KIND = "TPU v5 lite"


# ---------------------------------------------------------------- input specs
def input_specs(cfg, shape):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        if cfg.encoder_only:
            batch = {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)}
            if shape.kind == "train":
                batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        return batch
    # decode: cache at full kv length + one incoming token per sequence.
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    return {
        "cache": cache,
        "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }


def _dims(mesh):
    return (mesh.shape.get("data", 1), mesh.shape.get("model", 1),
            mesh.shape.get("pod", 1))


def batch_specs(cfg, shape, mesh):
    data, model, pod = _dims(mesh)
    dp = ("pod", "data") if pod > 1 else ("data",)
    B = shape.global_batch
    # shard batch over as much of the dp product as divides it.
    if B % (pod * data) == 0:
        bspec = dp
    elif B % data == 0:
        bspec = ("data",)
    else:
        bspec = None
    def spec(leaf):
        s = [bspec] + [None] * (leaf.ndim - 1)
        return P(*s)
    return spec


def _compile_step(cfg, shape, mesh, microbatches: int = 1):
    """Build the jitted step for this (cfg, shape) and compile on mesh."""
    params_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                                   jax.random.PRNGKey(0))
    pspecs = param_specs(params_shapes, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    params_structs = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shapes, psh)
    batch = input_specs(cfg, shape)

    with mesh_context(mesh):
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(adamw.init, params_shapes)
            ospecs = {"m": pspecs, "v": pspecs, "count": P()}
            osh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)
            opt_structs = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                opt_shapes, osh)
            bs = batch_specs(cfg, shape, mesh)
            bspecs = jax.tree.map(bs, batch)
            bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
            bstructs = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                batch, bsh)
            step = make_train_step(cfg, adamw.AdamWConfig(),
                                   num_microbatches=microbatches)
            jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_structs, opt_structs, bstructs)
        elif shape.kind == "prefill":
            bs = batch_specs(cfg, shape, mesh)
            bspecs = jax.tree.map(bs, batch)
            bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
            bstructs = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                batch, bsh)
            if cfg.encoder_only:
                fn = serve_steps.make_encode_step(cfg)
            else:
                fn = serve_steps.make_prefill_step(cfg, cache_len=shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(psh, bsh))
            lowered = jitted.lower(params_structs, bstructs)
        else:  # decode
            cache = jax.eval_shape(lambda: T.init_cache(cfg, shape.global_batch,
                                                        shape.seq_len))
            cspecs = cache_specs(cfg, shape, mesh, cache)
            csh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)
            cstructs = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                cache, csh)
            tok_s = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            idx_s = jax.ShapeDtypeStruct((), jnp.int32)
            fn = serve_steps.make_serve_step(cfg)
            jitted = jax.jit(fn, in_shardings=(psh, csh, None, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_structs, cstructs, tok_s, idx_s)
        return lowered.compile()


def cache_specs(cfg, shape, mesh, cache_shapes):
    """Shard decode caches: batch over dp; KVH or head_dim over model;
    for B=1 long-context, the sequence dim over data (sequence parallelism)."""
    data, model, pod = _dims(mesh)
    dp = ("pod", "data") if pod > 1 else ("data",)
    B = shape.global_batch

    def one(leaf):
        nd = leaf.ndim
        spec = [None] * nd
        # leading dim is the stacked segment axis (count), dim1 = batch.
        if nd >= 2 and leaf.shape[1] == B and B % (np.prod([mesh.shape[a] for a in dp])) == 0:
            spec[1] = dp
        if nd == 5:  # (seg, B, T, KVH, D)
            if leaf.shape[3] % model == 0 and leaf.shape[3] >= model:
                spec[3] = "model"
            elif leaf.shape[4] % model == 0:
                spec[4] = "model"
            if B == 1 and leaf.shape[2] % data == 0:
                spec[2] = "data"       # SP over the KV sequence
        elif nd == 4 and leaf.shape[2] > 4096:  # (seg, B, T, R) mla latents
            if B == 1 and leaf.shape[2] % data == 0:
                spec[2] = "data"
        elif nd == 3 and leaf.shape[2] > 4096:  # (seg, B, T) position rings
            if B == 1 and leaf.shape[2] % data == 0:
                spec[2] = "data"
        return P(*spec)

    return jax.tree.map(one, cache_shapes)


# ---------------------------------------------------------- cost correction
def _raw_costs(compiled):
    ca = compiled.cost_analysis()
    wires = analysis.collective_wire_bytes(compiled.as_text())
    return np.array([float(ca.get("flops", 0.0)),
                     float(ca.get("bytes accessed", 0.0)),
                     wires["ici"], wires["dcn"]])


def corrected_costs(cfg, base_compiled, compile_fn):
    """Scan-body trip-count correction for cost_analysis totals.

    XLA's HloCostAnalysis visits a while-loop body once, so a scanned
    segment of L layers contributes 1x, not Lx, to flops / bytes / parsed
    collective payloads.  We recover per-layer body costs by lowering one
    extra variant per distinct block kind with an appended 2-layer segment
    of that kind: body_k = cost(variant_k) - cost(base).  Then
        corrected = base + sum_k (layers_of_kind_k - segments_of_kind_k) * body_k
    (base already counts one body per *segment*).  Exact for flops, tight
    for bytes (fusion boundaries shift marginally).
    """
    import dataclasses as dc
    base = _raw_costs(base_compiled)
    kinds = {}
    for kind, count in cfg.segments:
        k = kinds.setdefault(kind, [0, 0])
        k[0] += count   # layers of this kind
        k[1] += 1       # segments of this kind
    corrected = base.copy()
    for kind, (layers, segs) in kinds.items():
        extra = layers - segs
        if extra <= 0:
            continue
        cfg_k = dc.replace(cfg, segments=cfg.segments + ((kind, 2),),
                           n_layers=cfg.n_layers + 2)
        variant = _raw_costs(compile_fn(cfg_k))
        body = np.maximum(variant - base, 0.0)
        corrected += extra * body
    return corrected


def blockwise_supplement(cfg, shape, n_devices: int):
    """Analytic per-device (flops, hbm_bytes) for blockwise-attention layers.

    The flash q/kv loops are HLO while-bodies (counted once by cost
    analysis); their true totals are data-independent and exactly known
    from the tile schedule, so we add them analytically.  The single tile
    the HLO did count is < 0.1% of the total and is not subtracted.
    """
    from ..models.blockwise_attn import analytic_costs, should_use_blockwise
    B = shape.global_batch
    H, D, KVH = cfg.n_heads, cfg.resolved_head_dim, cfg.n_kv_heads
    tot_f = tot_b = 0.0
    for kind_, count in cfg.segments:
        if kind_ not in ("dense", "swa", "moe", "moe_swa", "encoder",
                         "hybrid", "hybrid_global", "mla"):
            continue
        h_, d_, kvh_ = H, D, KVH
        if kind_ == "mla":
            if shape.kind == "decode":
                continue  # absorbed decode path: no blockwise loops
            d_ = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
            kvh_ = H
        if shape.kind in ("train", "prefill"):
            S = T = shape.seq_len
            mode = "train" if shape.kind == "train" else "serve"
        else:
            S = 1
            T = shape.seq_len
            if kind_ in ("swa", "moe_swa", "hybrid"):
                T = min(T, max(cfg.swa_window + 128, 256))
            mode = "serve"
        if not should_use_blockwise(B, S, T, h_):
            continue
        dtype_bytes = 1 if (shape.kind == "decode"
                            and cfg.kv_cache_dtype == "int8") else 2
        f, b = analytic_costs(B, S, T, h_, d_, kvh_, mode,
                              dtype_bytes=dtype_bytes)
        tot_f += f * count
        tot_b += b * count
    return tot_f / n_devices, tot_b / n_devices


# ------------------------------------------------------------------ lowering
def lower_cell(arch: str, shape_name: str, mesh, *, microbatches: int = 1,
               cfg=None, shape=None, cost_correct: bool = True):
    """Lower+compile one cell.  cfg/shape overrides support reduced-scale
    integration tests that exercise the identical code path."""
    cfg = cfg or registry.get_config(arch)
    shape = shape or SHAPES[shape_name]
    status = cell_status(cfg, shape)
    if status != "run":
        return {"arch": arch, "shape": shape_name, "status": status}

    n_dev = int(np.prod(list(mesh.shape.values())))
    tokens_per_step = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)

    # flops/bytes/collectives are microbatch-invariant in reality but the
    # microbatch scan body is HLO-counted once, so the *cost* artifact is
    # always lowered at mb=1; the *memory* artifact uses the requested mb.
    def compile_for(c):
        return _compile_step(c, shape, mesh, 1)

    t0 = time.time()
    compiled = compile_for(cfg)
    if microbatches > 1 and shape.kind == "train":
        compiled_mem = _compile_step(cfg, shape, mesh, microbatches)
    else:
        compiled_mem = compiled
    compile_s = time.time() - t0

    if cost_correct:
        flops, bytes_acc, ici, dcn = corrected_costs(cfg, compiled, compile_for)
    else:
        flops, bytes_acc, ici, dcn = _raw_costs(compiled)
    sup_f, sup_b = blockwise_supplement(cfg, shape, n_dev)
    flops += sup_f
    bytes_acc += sup_b

    mf = analysis.model_flops(cfg, tokens_per_step,
                              "train" if shape.kind == "train" else "serve")
    mem = compiled_mem.memory_analysis()
    peak = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    roof = analysis.analyze_from(
        flops=flops, hbm_bytes=bytes_acc, ici_bytes=ici, dcn_bytes=dcn,
        peak_mem=peak, n_devices=n_dev, model_flops_total=mf,
        by_kind=analysis.collective_wire_bytes(compiled.as_text())["by_kind"],
        device_kind=TARGET_DEVICE_KIND)
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "n_devices": n_dev,
        "compile_s": round(compile_s, 1),
        "tokens_per_step": tokens_per_step,
        "memory_analysis": {
            "argument_gib": mem.argument_size_in_bytes / 2**30,
            "output_gib": mem.output_size_in_bytes / 2**30,
            "temp_gib": mem.temp_size_in_bytes / 2**30,
            "peak_gib": roof.peak_mem_bytes / 2**30,
        },
        "roofline": roof.as_dict(),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--kv-int8", action="store_true",
                    help="quantized int8 decode KV cache")
    ap.add_argument("--out", default="runs/dryrun.jsonl")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    archs = registry.list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    done = set()
    if os.path.exists(args.out):
        for line in open(args.out):
            r = json.loads(line)
            done.add((r["arch"], r["shape"], r.get("mesh_kind", r.get("mesh"))))

    with open(args.out, "a") as f:
        for mesh_kind in meshes:
            mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
            for arch in archs:
                for shape in shapes:
                    key = (arch, shape, mesh_kind)
                    if key in done:
                        continue
                    t0 = time.time()
                    try:
                        cfg_cell = registry.get_config(arch)
                        if args.kv_int8:
                            import dataclasses as _dc
                            cfg_cell = _dc.replace(cfg_cell,
                                                   kv_cache_dtype="int8")
                        rec = lower_cell(arch, shape, mesh, cfg=cfg_cell,
                                         microbatches=args.microbatches)
                    except Exception as e:  # record failures; they are bugs
                        rec = {"arch": arch, "shape": shape, "status": "FAIL",
                               "error": f"{type(e).__name__}: {e}",
                               "trace": traceback.format_exc()[-2000:]}
                    rec["mesh_kind"] = mesh_kind
                    rec["wall_s"] = round(time.time() - t0, 1)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    status = rec["status"]
                    extra = ""
                    if status == "ok":
                        r = rec["roofline"]
                        extra = (f" peak={rec['memory_analysis']['peak_gib']:.2f}GiB"
                                 f" bottleneck={r['bottleneck']}"
                                 f" t=({r['t_compute']:.4f},{r['t_memory']:.4f},"
                                 f"{r['t_collective']:.4f})s")
                    elif status == "FAIL":
                        extra = " " + rec["error"][:200]
                    print(f"[{mesh_kind}] {arch} x {shape}: {status}"
                          f" ({rec['wall_s']}s){extra}", flush=True)


if __name__ == "__main__":
    main()
