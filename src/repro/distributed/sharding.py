"""Sharding rules: logical axes -> mesh axes, GSPMD constraints, param specs.

Mesh axes (launch/mesh.py):
  pod    — DCN axis across pods: pure data parallel (gradient all-reduce
           over the slow interconnect only once per step).
  data   — FSDP: batch + fully-sharded parameters/optimizer state.
  model  — TP/EP: attention heads, MLP hidden, MoE experts, vocab.

``PARAM_RULES`` maps parameter-name suffixes to PartitionSpecs; anything
unmatched is replicated.  Activations get explicit constraints at block
boundaries via :func:`constrain` (a no-op outside a mesh context so models
run unsharded on a single CPU device in tests).
"""
from __future__ import annotations

import re

import jax
import numpy as np

from ..launch.mesh import current_mesh
from jax.sharding import PartitionSpec as P

#: logical -> physical for activations (tuples = joint axes, e.g. the
#: data-parallel product ("pod", "data") for batch/group dims).
ACT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
}


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool | None = None):
    """``jax.shard_map``; ``axis_names`` is the set of *manual* axes (None =
    all of them), and ``check_vma=None`` keeps JAX's default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names, **kw)


def mesh_axis_size(name: str) -> int:
    mesh = current_mesh()
    if mesh is None or mesh.empty or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def constrain(x, *logical):
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None or mesh.empty:
        return x
    # Inside a partial-manual shard_map (the compressed-gradient pod loop)
    # activation constraints are dropped entirely: mixing them with manual
    # axes trips an XLA SPMD-partitioner CHECK (spmd_partitioner_util.cc:504,
    # jaxlib 0.8.2); GSPMD still propagates sharding from the in/out specs.
    if any(t == jax.sharding.AxisType.Manual for t in mesh.axis_types):
        return x
    manual = set()
    spec = []
    for dim, l in zip(x.shape, logical):
        phys = ACT_RULES.get(l) if l is not None else None
        if phys is None:
            spec.append(None)
            continue
        axes = tuple(a for a in ((phys,) if isinstance(phys, str) else phys)
                     if a in mesh.shape and a not in manual)
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and dim % size == 0:
            spec.append(axes if len(axes) > 1 else axes[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# --------------------------------------------------------------- param rules
# suffix-pattern -> spec builder (rank-aware).  Stacked (scan) params have a
# leading layer dim, handled by _pad_spec.
PARAM_RULES: list[tuple[str, tuple]] = [
    # attention projections: shard the head/feature product dim over model,
    # the d_model dim over data (FSDP).
    (r"\.attn\.wq$", ("data", "model")),
    (r"\.attn\.wk$", ("data", "model")),
    (r"\.attn\.wv$", ("data", "model")),
    (r"\.attn\.wo$", ("model", "data")),
    # MLA
    (r"\.attn\.wq_down$", ("data", "model")),
    (r"\.attn\.wq_up$", (None, "model")),
    (r"\.attn\.wkv_down$", ("data", None)),
    (r"\.attn\.wk_up$", (None, "model")),
    (r"\.attn\.wv_up$", (None, "model")),
    # dense MLP
    (r"\.mlp\.wi$", ("data", "model")),
    (r"\.mlp\.wg$", ("data", "model")),
    (r"\.mlp\.wo$", ("model", "data")),
    # MoE: experts over model (EP); when E doesn't divide the model axis
    # (mixtral: 8 experts on a 16-way axis) fall back to tensor-parallel
    # expert FFNs (hidden dim over model) — candidate list, first valid wins.
    (r"\.moe\.router$", (None, None)),
    (r"\.moe\.wi$", [("model", "data", None), (None, "data", "model")]),
    (r"\.moe\.wg$", [("model", "data", None), (None, "data", "model")]),
    (r"\.moe\.wo$", [("model", None, "data"), (None, "model", "data")]),
    (r"\.moe\.shared\.wi$", ("data", "model")),
    (r"\.moe\.shared\.wg$", ("data", "model")),
    (r"\.moe\.shared\.wo$", ("model", "data")),
    # xLSTM / SSM
    (r"\.cell\.wq$", ("data", "model")),
    (r"\.cell\.wk$", ("data", "model")),
    (r"\.cell\.wv$", ("data", "model")),
    (r"\.cell\.w_in$", ("data", "model")),
    (r"\.cell\.w_bcdt$", ("model", None)),
    (r"\.cell\.w_out$", ("model", "data")),
    (r"\.cell\.wz$", ("data", "model")),
    (r"\.cell\.wi$", ("data", "model")),
    (r"\.cell\.wf$", ("data", "model")),
    (r"\.cell\.wo_gate$", ("data", "model")),
    (r"\.cell\.r$", ("data", "model")),
    (r"\.cell\.wo$", ("model", "data")),
    # embeddings: vocab over model, features over data.
    (r"^embed$", ("model", "data")),
    (r"^unembed$", ("data", "model")),
]


def _candidates_for(path: str, ndim: int, stacked: bool):
    """Ordered candidate specs for a parameter path (first valid wins)."""
    for pat, spec in PARAM_RULES:
        if re.search(pat, path):
            cands = spec if isinstance(spec, list) else [spec]
            out = []
            for c in cands:
                c = tuple(c)
                if stacked:
                    c = (None,) + c  # leading scan-layer dim
                if len(c) < ndim:
                    c = c + (None,) * (ndim - len(c))
                out.append(c[:ndim])
            return out
    return [(None,) * ndim]


def _validate(spec, shape, mesh):
    fixed, full = [], True
    for dim, ax in zip(shape, spec):
        ok = ax is not None and ax in mesh.shape and dim % mesh.shape[ax] == 0
        fixed.append(ax if ok else None)
        if ax is not None and not ok:
            full = False
    return tuple(fixed), full


def param_specs(params, mesh=None):
    """PartitionSpec pytree for a parameter pytree (paths drive the rules).

    Each rule may list fallback candidates; the first whose named axes all
    divide the tensor is used, otherwise non-dividing axes of the best
    candidate are dropped (tiny smoke configs on big meshes).
    """
    mesh = mesh or current_mesh()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def path_str(kp):
        return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)

    specs = {}
    for kp, leaf in flat:
        path = path_str(kp)
        stacked = path.startswith("seg")  # scanned segment params: leading L dim
        cands = _candidates_for(path, leaf.ndim, stacked)
        if mesh is None or mesh.empty:
            specs[path] = P(*cands[0])
            continue
        chosen = None
        for c in cands:
            fixed, full = _validate(c, leaf.shape, mesh)
            if full:
                chosen = fixed
                break
        if chosen is None:
            chosen, _ = _validate(cands[0], leaf.shape, mesh)
        specs[path] = P(*chosen)

    # rebuild tree
    treedef = jax.tree_util.tree_structure(params)
    leaves = [specs[path_str(kp)] for kp, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
