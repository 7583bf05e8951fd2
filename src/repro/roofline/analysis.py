"""Three-term roofline from compiled dry-run artifacts (no TPU required).

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / link_bw   (ICI vs DCN per group span)

FLOPs/bytes come from ``compiled.cost_analysis()`` (the *per-device*
partitioned module).  Collective payloads are not in cost_analysis, so we
parse the HLO: every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op's tensor shapes, converted to per-device wire bytes
with ring-algorithm factors:

  all-reduce      2 * s * (g-1)/g      (reduce-scatter + all-gather phases)
  all-gather          r * (g-1)/g      (r = result bytes)
  reduce-scatter      s * (g-1)/g      (s = operand bytes)
  all-to-all          s * (g-1)/g
  collective-permute  s

Groups whose device ids span a pod boundary (stride >= 256 in our meshes)
are charged to DCN instead of ICI.
"""
from __future__ import annotations

import dataclasses
import re

from . import hardware as hw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?([a-z0-9\[\],]+\s+)?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_info(line: str, pod_stride: int = 256):
    """(group_size, crosses_pod).  Defaults to (1, False) if unparseable."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, g = int(m.group(1)), int(m.group(2))
        # iota groups [n,g]<=[N]: consecutive ids; crosses pod iff a group
        # spans ids differing by >= pod_stride.
        return g, g > pod_stride
    m = _GROUPS_RE.search(line)
    if not m:
        return 1, False
    first = m.group(1).split("}")[0].strip("{} ")
    ids = [int(x) for x in first.split(",") if x.strip()]
    if not ids:
        return 1, False
    crosses = (max(ids) - min(ids)) >= pod_stride
    return len(ids), crosses


def collective_wire_bytes(hlo_text: str, pod_stride: int = 256) -> dict:
    """Per-device wire bytes, split by fabric and op kind."""
    out = {"ici": 0.0, "dcn": 0.0, "by_kind": {}}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if "-done(" in line:
            continue  # -start carries the shapes; -done would double count
        kind = m.group(2)
        g, crosses = _group_info(line, pod_stride)
        if g <= 1:
            continue
        lhs, _, rhs = line.partition("=")
        result_b = _shape_bytes(rhs.split("(")[0]) or _shape_bytes(lhs)
        operand_b = _shape_bytes(rhs.split("(", 1)[1]) if "(" in rhs else 0
        frac = (g - 1) / g
        if kind == "all-reduce":
            wire = 2 * operand_b * frac
        elif kind == "all-gather":
            wire = result_b * frac
        elif kind == "collective-permute":
            wire = operand_b
        else:  # reduce-scatter, all-to-all
            wire = operand_b * frac
        fabric = "dcn" if crosses else "ici"
        out[fabric] += wire
        k = out["by_kind"].setdefault(kind, {"count": 0, "bytes": 0.0})
        k["count"] += 1
        k["bytes"] += wire
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    hbm_bytes_per_dev: float
    ici_bytes_per_dev: float
    dcn_bytes_per_dev: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops_total: float
    useful_flops_ratio: float
    peak_mem_bytes: int
    by_kind: dict

    def as_dict(self):
        return dataclasses.asdict(self)


def model_flops(cfg, tokens: int, kind: str) -> float:
    """6*N*D (training) / 2*N*D (inference fwd) with N = *active* params."""
    n_active = active_params(cfg)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_active * tokens


def active_params(cfg) -> float:
    """Parameter count actually touched per token (MoE: top-k + shared)."""
    from ..models import registry  # lazy; avoids cycles
    import jax
    import numpy as np
    from ..models import transformer as T
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    total = 0.0
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for kp, leaf in flat:
        path = ".".join(str(getattr(k, "key", k)) for k in kp)
        n = float(np.prod(leaf.shape))
        if ".moe.w" in path and ".shared." not in path:
            n *= cfg.top_k / max(1, cfg.n_experts)   # routed experts
        total += n
    return total


def analyze_from(*, flops: float, hbm_bytes: float, ici_bytes: float,
                 dcn_bytes: float, peak_mem: int, n_devices: int,
                 model_flops_total: float, by_kind: dict,
                 device_kind: str) -> Roofline:
    """Roofline from (possibly trip-count-corrected) per-device totals,
    against the published peaks of ``device_kind``."""
    chip = hw.peaks(device_kind)
    t_c = flops / chip.flops_bf16
    t_m = hbm_bytes / chip.hbm_bw
    t_x = ici_bytes / chip.ici_bw_per_link + dcn_bytes / hw.DCN_BW_PER_HOST
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops_total / max(1.0, flops * n_devices)
    return Roofline(flops, hbm_bytes, ici_bytes, dcn_bytes,
                    t_c, t_m, t_x, bottleneck, model_flops_total, useful,
                    peak_mem, by_kind)


def analyze(compiled, *, n_devices: int, model_flops_total: float,
            device_kind: str, pod_stride: int = 256) -> Roofline:
    """Single-artifact roofline (no scan correction — see dryrun for that)."""
    ca = compiled.cost_analysis()
    wires = collective_wire_bytes(compiled.as_text(), pod_stride)
    mem = compiled.memory_analysis()
    peak = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    return analyze_from(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        ici_bytes=wires["ici"], dcn_bytes=wires["dcn"], peak_mem=peak,
        n_devices=n_devices, model_flops_total=model_flops_total,
        by_kind=wires["by_kind"], device_kind=device_kind)
