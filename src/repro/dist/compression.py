"""Gradient compression: int8 quantization + error-feedback all-reduce.

Data-parallel replicas quantize their local gradients to int8 (per-tensor
absmax scale), all-reduce the dequantized values, and keep the rounding
residual ON-DEVICE for the next step (error feedback / EF-SGD), which
keeps the compressed optimizer trajectory unbiased in the long run.

What this validates is the EF-SGD *numerics* (quantize -> dequantize ->
mean-reduce, residual carried locally): XLA has no int8 ring-all-reduce
primitive, so the reduced payload here is the dequantized f32 — wire-level
int8 transport is a backend/collective-implementation concern.  Traffic is
therefore the same as an exact ``psum`` while the quantization error and
its feedback loop are modeled exactly.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_EPS = 1e-12


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (f32) -> (q int8, scale f32 scalar); round-to-nearest with
    per-tensor absmax scale, so |dequant - x| <= scale / 2."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), _EPS) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def init_error_buffers(params, n_shards: int = 1):
    """Zeroed error-feedback residuals: one per parameter tensor per
    replica (leading ``n_shards`` axis, sharded over the data axis by
    ``make_compressed_grad_fn``)."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_shards,) + tuple(jnp.shape(p)), jnp.float32),
        params)


def make_compressed_grad_fn(loss_fn, mesh, axis_name: str):
    """Build ``fn(params, batch, errors) -> (loss, grads, new_errors)``.

    The batch and the error buffers shard along ``axis_name``; each
    replica computes its local gradient, adds its own residual, quantizes
    to int8, and the dequantized tensors are mean-all-reduced.  The new
    residual is each replica's local rounding error, fed back on the next
    call.  ``errors`` must come from ``init_error_buffers(params,
    n_shards=<axis size>)``.

    The sharded computation is jitted once per (params, batch, errors)
    tree structure and cached — calling it in a training loop hits the
    jit cache instead of retracing every step.
    """
    vg = jax.value_and_grad(loss_fn)

    def local(params, batch, errors):
        loss, grads = vg(params, batch)
        leaves, treedef = jax.tree.flatten(grads)
        err_leaves = jax.tree.leaves(errors)     # local shard: (1, *shape)
        out, new_err = [], []
        for g, e in zip(leaves, err_leaves):
            c = g + e[0]
            q, s = quantize_int8(c)
            deq = dequantize_int8(q, s)
            out.append(jax.lax.pmean(deq, axis_name))
            new_err.append((c - deq)[None])      # residual stays local
        return (jax.lax.pmean(loss, axis_name),
                jax.tree.unflatten(treedef, out),
                jax.tree.unflatten(treedef, new_err))

    cache = {}
    axis_size = mesh.shape[axis_name]

    def fn(params, batch, errors):
        err_dim = jax.tree.leaves(errors)[0].shape[0]
        if err_dim != axis_size:
            raise ValueError(
                f"error buffers have leading dim {err_dim} but the "
                f"{axis_name!r} mesh axis has {axis_size} shards — build "
                f"them with init_error_buffers(params, n_shards={axis_size})")
        key = jax.tree.structure((params, batch, errors))
        compiled = cache.get(key)
        if compiled is None:
            rep = lambda tree: jax.tree.map(lambda _: P(), tree)
            shd = lambda tree: jax.tree.map(lambda _: P(axis_name), tree)
            compiled = jax.jit(jax.shard_map(
                local, mesh=mesh,
                in_specs=(rep(params), shd(batch), shd(errors)),
                out_specs=(P(), rep(params), shd(errors)),
                check_vma=False))
            cache[key] = compiled
        return compiled(params, batch, errors)

    return fn
