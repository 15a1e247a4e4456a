"""Pallas TPU kernel: chunked selective scan (Mamba-1/2).

Recurrence (diag-A selective SSM, shared by mamba1 and mamba2 — see
``repro.models.mamba``):

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t
    y_t = <h_t, C_t>

TPU layout: channels D on the lane axis (128-multiples), state index N on
sublanes — per time step the update is an (N, Dblk) elementwise VPU op.
Grid = (batch, D blocks, L chunks) with chunks innermost: TPU executes the
grid sequentially, so the f32 state lives in VMEM scratch across chunks
(reset at chunk 0).  The inner ``fori_loop`` walks the chunk; HBM traffic
is chunk-granular (x/dt/B/C tiles stream in, y tiles stream out) while the
state never leaves VMEM — this is the TPU-native replacement for the CUDA
kernel's shared-memory state of the original Mamba implementation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, y_ref, hout_ref, h_ref,
                *, chunk: int, n_chunks: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    at = at_ref[...].astype(jnp.float32)             # (N, Dblk)

    def body(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)     # (Dblk,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)   # (Dblk,)
        b_t = b_ref[0, t, :].astype(jnp.float32)     # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)     # (N,)
        decay = jnp.exp(dt_t[None, :] * at)          # (N, Dblk)
        h = decay * h + (dt_t * x_t)[None, :] * b_t[:, None]
        y_ref[0, t, :] = jnp.sum(h * c_t[:, None], axis=0)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    h_ref[...] = h

    @pl.when(c_idx == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit,
                   static_argnames=("chunk", "d_block", "interpret"))
def ssm_scan_pallas(x, dt, A, B, C, *, chunk: int = 128, d_block: int = 256,
                    interpret: bool = False):
    """x, dt: (Bt, L, D); A: (D, N); B, C: (Bt, L, N).
    Returns (y (Bt, L, D) f32, h_final (Bt, D, N) f32)."""
    Bt, L, D = x.shape
    N = A.shape[1]
    d_block = min(d_block, _round_up(D, 128))
    Dp = _round_up(D, d_block)
    Lp = _round_up(L, chunk)
    Np = _round_up(N, 8)

    x = jnp.pad(x, ((0, 0), (0, Lp - L), (0, Dp - D)))
    dt = jnp.pad(dt, ((0, 0), (0, Lp - L), (0, Dp - D)))
    at = jnp.pad(A.T, ((0, Np - N), (0, Dp - D)))      # (Np, Dp); pad A=0
    b = jnp.pad(B, ((0, 0), (0, Lp - L), (0, Np - N)))
    c = jnp.pad(C, ((0, 0), (0, Lp - L), (0, Np - N)))

    n_chunks = Lp // chunk
    n_d = Dp // d_block

    y, hout = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=(Bt, n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b_, d, c_: (b_, c_, d)),
            pl.BlockSpec((1, chunk, d_block), lambda b_, d, c_: (b_, c_, d)),
            pl.BlockSpec((Np, d_block), lambda b_, d, c_: (0, d)),
            pl.BlockSpec((1, chunk, Np), lambda b_, d, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, Np), lambda b_, d, c_: (b_, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b_, d, c_: (b_, c_, d)),
            pl.BlockSpec((1, Np, d_block), lambda b_, d, c_: (b_, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, Lp, Dp), jnp.float32),
            jax.ShapeDtypeStruct((Bt, Np, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Np, d_block), jnp.float32)],
        interpret=interpret,
    )(x, dt, at, b, c)
    return y[:, :L, :D], jnp.swapaxes(hout, 1, 2)[:, :D, :N]
