"""Cross-pod gradient compression: int8 all-reduce with error feedback.

At 2+ pods the gradient all-reduce crosses the (slow) inter-pod links; the
standard distributed-optimization trick is to quantize the cross-pod leg to
int8 with a per-tensor scale and carry the quantization error into the next
step (error feedback keeps SGD/Adam convergence).  Implemented as a
``shard_map`` over the 'pod' axis: the f32 within-pod reduction stays
untouched (GSPMD handles it as part of backward); only the pod-axis psum
runs on int8 payloads (accumulated in int32 — exact for <=2^23 pods).

Validated in tests/test_compression.py: (a) dequantized psum error is
bounded by the quantization step, (b) error feedback makes the *cumulative*
compressed sum track the true sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def quantize(x: jax.Array):
    """Per-tensor symmetric int8 quantization.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_leaf(x, axis_name: str):
    """int8-quantized psum over ``axis_name`` (inside shard_map).

    Scales differ per shard, so each shard dequantizes with its own scale
    after an int32 psum of q and a f32 psum of scales... exactness requires
    a shared scale: we psum-max the scale first (one scalar per tensor —
    negligible traffic), then quantize against the shared scale.
    """
    scale = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return s.astype(jnp.float32) * scale


def compressed_crosspod_allreduce(grads_stacked, mesh, pod_axis: str = "pod",
                                  error_fb=None):
    """Mean-all-reduce per-pod gradients over the pod axis, int8 payloads +
    error feedback.

    ``grads_stacked`` leaves are (n_pod, ...) — one slice per pod, sharded
    over ``pod_axis`` on axis 0 (each pod's within-pod reduction result).
    ``error_fb`` has the same shape (zeros at step 0).

    Returns (mean_grads (leaves (1, ...), replicated), new_error_fb).
    """
    if error_fb is None:
        error_fb = jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads_stacked)

    n_pod = mesh.shape[pod_axis]

    def leaf_fn(g, e):  # local views: (1, ...)
        x = g.astype(jnp.float32) + e  # error feedback: re-inject residual
        scale = jax.lax.pmax(jnp.max(jnp.abs(x)), pod_axis) / 127.0
        scale = jnp.maximum(scale, 1e-20)
        q = jnp.clip(jnp.round(x / scale), -127, 127)
        new_e = x - q * scale  # residual carried to next step
        summed = jax.lax.psum(q.astype(jnp.int32), pod_axis)
        mean = summed.astype(jnp.float32) * scale / n_pod
        return mean.astype(g.dtype), new_e

    flat, treedef = jax.tree.flatten(grads_stacked)
    eflat, _ = jax.tree.flatten(error_fb)

    def body(gs, es):
        outs = [leaf_fn(g, e) for g, e in zip(gs, es)]
        return tuple(o[0] for o in outs), tuple(o[1] for o in outs)

    pod_spec = lambda x: P(*([pod_axis] + [None] * (x.ndim - 1)))
    rep_spec = lambda x: P(*([None] * x.ndim))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tuple(pod_spec(x) for x in flat),
                  tuple(pod_spec(x) for x in eflat)),
        out_specs=(tuple(rep_spec(x) for x in flat),
                   tuple(pod_spec(x) for x in eflat)),
    )
    synced, new_e = fn(tuple(flat), tuple(eflat))
    return (jax.tree.unflatten(treedef, list(synced)),
            jax.tree.unflatten(treedef, list(new_e)))
