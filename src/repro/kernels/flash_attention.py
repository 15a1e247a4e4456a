"""Pallas TPU flash attention (causal GQA, optional sliding window).

Online-softmax tiling: grid (batch*q_heads, q_blocks, kv_blocks) with the
kv axis innermost — TPU grids execute sequentially, so the f32 accumulator,
row-max and row-sum live in VMEM scratch across kv steps.  Blocks that are
fully masked (above the causal diagonal, or entirely left of the sliding
window) are skipped with ``pl.when`` — for SWA this makes long-sequence
prefill linear in S.

MXU alignment: q/k/v tiles are (block, head_dim) with head_dim in
{64, 120, 128, 160}; blocks default to 128x128.  f32 accumulation, inputs
bf16 or f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, block_q: int, block_k: int, n_kv: int, s_real: int,
                  window: int, causal: bool, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)
    q_start = i * block_q
    kv_start = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block relevance (traced): causal upper-triangular skip + window skip
    relevant = kv_start < jnp.minimum(s_real, q_start + block_q) \
        if causal else kv_start < s_real
    if window:
        relevant &= kv_start + block_k > q_start + 1 - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (BQ, D)
        k = k_ref[0].astype(jnp.float32)            # (BK, D)
        v = v_ref[0].astype(jnp.float32)            # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        ok = kpos < s_real
        if causal:
            ok &= kpos <= qpos
        if window:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, _NEG)

        m_prev = m_ref[...]                          # (BQ, LANES)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)[:, None]          # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)              # (BQ, LANES)
        p = jnp.exp(s - m_new[:, :1])                # (BQ, BK)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)[:, None]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    Sp = _round_up(S, max(block_q, block_k))
    if Sp != S:
        pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    qf = q.reshape(B * Hq, Sp, D)
    kf = k.reshape(B * Hkv, Sp, D)
    vf = v.reshape(B * Hkv, Sp, D)
    n_q, n_kv = Sp // block_q, Sp // block_k

    def kv_index(bh, i, j):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // group, j, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          n_kv=n_kv, s_real=S, window=window, causal=causal,
                          scale=scale),
        grid=(B * Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sp, D)[:, :, :S]
