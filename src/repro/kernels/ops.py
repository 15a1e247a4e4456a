"""jit'd public wrappers for the Pallas kernels.

Every wrapper compiles its kernel with Mosaic unless the caller passes
``interpret=True``, which runs the kernel body in the Pallas interpreter.
Nothing picks the mode from the backend: a compiled kernel requested off
a TPU raises (Pallas: "Only interpret mode is supported on CPU backend")
instead of quietly running interpreted.  Flags that route a whole model or
search through a kernel (``use_kernel``, ``use_flash``) take the value
:data:`INTERPRET` for the interpreted kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.makespan import makespan_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


#: ``use_kernel`` / ``use_flash`` value that runs the Pallas kernel in the
#: interpreter (tests off the TPU); ``True`` compiles it with Mosaic.
INTERPRET = "interpret"


# ---------------------------------------------------------------------------
# makespan (M3E fitness hot-loop)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("num_accels", "interpret"))
def population_makespan(accel, prio, lat, bw, bw_sys, num_accels: int,
                        interpret: bool = False):
    """Drop-in replacement for ``bw_allocator.simulate_population``.

    accel: (P, G) int32, prio: (P, G) f32, lat/bw: (G, A) f32 job tables.
    Queue decode (argsort) runs in XLA; the event simulation runs in the
    Pallas kernel with the queue tables resident in VMEM."""
    from repro.core.encoding import decode

    lat = lat.astype(jnp.float32)
    bw = jnp.maximum(bw.astype(jnp.float32), 1e-3)

    def decode_one(a, p):
        sched = decode(a, p, num_accels)
        qlat = jnp.take_along_axis(lat.T, sched.queue, axis=1)
        qbw = jnp.take_along_axis(bw.T, sched.queue, axis=1)
        return qlat, qbw, sched.count

    qlat, qbw, count = jax.vmap(decode_one)(accel, prio)
    return makespan_pallas(qlat, qbw, count, bw_sys, interpret=interpret)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) -> (B, S, Hq, D).

    Layout matches ``repro.models.layers`` (seq-major heads); the kernel
    operates on (B, H, S, D)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq = min(block_q, max(16, qt.shape[2]))
    bk = min(block_k, max(16, kt.shape[2]))
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------
def ssm_scan(x, dt, A, B, C, *, chunk: int = 128, d_block: int = 256,
             interpret: bool = False):
    """Same contract as ``repro.models.mamba.selective_scan``."""
    ch = min(chunk, max(8, x.shape[1]))
    return ssm_scan_pallas(x, dt, A, B, C, chunk=ch, d_block=d_block,
                           interpret=interpret)
