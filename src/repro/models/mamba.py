"""Mamba-1 (falcon-mamba), Mamba-2 blocks, and the Zamba2 hybrid
(Mamba-2 backbone + one weight-tied shared attention block applied every
``shared_attn_every`` layers).

The selective scan has three implementations:
  - ``selective_scan``      lax.scan over time (reference; used for train /
                            prefill on any backend),
  - ``kernels/ssm_scan``    Pallas TPU chunked kernel (opt-in via
                            ``cfg.use_flash``),
  - a single-step update for decode (state carried in the cache).

State convention: h (B, d_inner, N) float32;
  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t ;  y_t = <h_t, C_t>.
Mamba-2 reuses the same recurrence with per-head scalar A broadcast over
channels and head-shared dt.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.models.module import KeyGen, Param, param, ones_init, scan_or_unroll, zeros_init


# ---------------------------------------------------------------------------
# selective scan (shared by mamba1/mamba2)
# ---------------------------------------------------------------------------
def selective_scan(x, dt, A, B, C, h0=None):
    """x, dt: (Bt, S, Di); A: (Di, N); B, C: (Bt, S, N) -> (y, h_final)."""
    Bt, S, Di = x.shape
    N = A.shape[1]
    h0 = jnp.zeros((Bt, Di, N), jnp.float32) if h0 is None else h0
    Af = A.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        decay = jnp.exp(dt_t[..., None] * Af[None])          # (Bt, Di, N)
        h = decay * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
        y = jnp.sum(h * C_t[:, None, :], axis=-1)            # (Bt, Di)
        return h, y

    xs = (jnp.swapaxes(x.astype(jnp.float32), 0, 1),
          jnp.swapaxes(dt.astype(jnp.float32), 0, 1),
          jnp.swapaxes(B.astype(jnp.float32), 0, 1),
          jnp.swapaxes(C.astype(jnp.float32), 0, 1))
    h, ys = jax.lax.scan(step, h0, xs)
    return jnp.swapaxes(ys, 0, 1), h


def selective_scan_chunked(x, dt, A, B, C, h0=None, chunk: int = 16):
    """Selective scan with the time axis processed ``chunk`` steps per
    lax.scan iteration, the inner steps unrolled straight-line.

    Numerically identical to ``selective_scan`` (same op order), but XLA
    fuses each unrolled chain into one kernel: per-step intermediates stay
    on-chip, the carried state is read/written once per *chunk* instead of
    once per step, and the while-loop trip count drops S -> S/chunk.  This
    is the pure-XLA mitigation of the SSM time-scan HBM wall (the full fix
    is the Pallas ``ssm_scan`` kernel, which also keeps the state in VMEM
    across chunks)."""
    Bt, S, Di = x.shape
    if S % chunk != 0:
        return selective_scan(x, dt, A, B, C, h0)
    N = A.shape[1]
    h0 = jnp.zeros((Bt, Di, N), jnp.float32) if h0 is None else h0
    Af = A.astype(jnp.float32)

    def to_chunks(a):
        t = jnp.swapaxes(a.astype(jnp.float32), 0, 1)   # (S, Bt, ...)
        return t.reshape((S // chunk, chunk) + t.shape[1:])

    xs = (to_chunks(x), to_chunks(dt), to_chunks(B), to_chunks(C))

    def body(h, inp):
        xc, dtc, Bc, Cc = inp                      # (chunk, Bt, ...)
        ys = []
        for t in range(chunk):                     # unrolled -> one fusion
            decay = jnp.exp(dtc[t][..., None] * Af[None])
            h = decay * h + (dtc[t] * xc[t])[..., None] * Bc[t][:, None, :]
            ys.append(jnp.sum(h * Cc[t][:, None, :], axis=-1))
        return h, jnp.stack(ys)

    h, ys = jax.lax.scan(body, h0, xs)
    return jnp.swapaxes(ys.reshape(S, Bt, Di), 0, 1), h


def selective_step(h, x_t, dt_t, A, B_t, C_t):
    """One decode step: x_t, dt_t (Bt, Di); B_t, C_t (Bt, N)."""
    decay = jnp.exp(dt_t[..., None].astype(jnp.float32) * A.astype(jnp.float32)[None])
    h = decay * h + (dt_t * x_t)[..., None].astype(jnp.float32) * B_t[:, None, :].astype(jnp.float32)
    y = jnp.sum(h * C_t[:, None, :].astype(jnp.float32), axis=-1)
    return h, y


def causal_conv1d(x, w, b):
    """Depthwise causal conv: x (Bt,S,Di), w (Di,W), b (Di,)."""
    W = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[None, None, :, i].squeeze(1)
              for i in range(W))
    return out + b[None, None]


def conv1d_step(conv_state, x_t, w, b):
    """conv_state: (Bt, W-1, Di) trailing inputs; x_t: (Bt, Di)."""
    full = jnp.concatenate([conv_state, x_t[:, None]], axis=1)   # (Bt, W, Di)
    out = jnp.einsum("bwd,dw->bd", full, w) + b[None]
    return full[:, 1:], out


# ---------------------------------------------------------------------------
# Mamba-1 block (falcon-mamba)
# ---------------------------------------------------------------------------
class Mamba1Params(NamedTuple):
    norm: Param          # (L, d)
    in_proj: Param       # (L, d, 2*Di)
    conv_w: Param        # (L, Di, W)
    conv_b: Param        # (L, Di)
    x_proj: Param        # (L, Di, dt_rank + 2N)
    dt_w: Param          # (L, dt_rank, Di)
    dt_b: Param          # (L, Di)
    A_log: Param         # (L, Di, N)
    D: Param             # (L, Di)
    out_proj: Param      # (L, Di, d)


def init_mamba1(kg: KeyGen, cfg: ModelConfig) -> Mamba1Params:
    Lr, d, Di, N = cfg.num_layers, cfg.d_model, cfg.inner, cfg.ssm_state
    dtr, W, dt = cfg.dtr, cfg.conv_width, cfg.dtype_jnp

    def a_init(key, shape, dtype):
        a = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32),
                             shape)
        return jnp.log(a).astype(dtype)

    return Mamba1Params(
        norm=L.init_rmsnorm(kg, Lr, d, dt),
        in_proj=param(kg, (Lr, d, 2 * Di), ("layers", "embed", "inner"), dt,
                      stddev=d ** -0.5),
        conv_w=param(kg, (Lr, Di, W), ("layers", "inner", "conv"), dt,
                     stddev=W ** -0.5),
        conv_b=param(kg, (Lr, Di), ("layers", "inner"), dt, init=zeros_init),
        x_proj=param(kg, (Lr, Di, dtr + 2 * N), ("layers", "inner", None), dt,
                     stddev=Di ** -0.5),
        dt_w=param(kg, (Lr, dtr, Di), ("layers", "dt_rank", "inner"), dt,
                   stddev=dtr ** -0.5),
        dt_b=param(kg, (Lr, Di), ("layers", "inner"), jnp.float32,
                   init=lambda k, s, _: jnp.log(
                       jnp.expm1(jnp.full(s, 1e-2, jnp.float32)))),
        A_log=param(kg, (Lr, Di, N), ("layers", "inner", "ssm_state"),
                    jnp.float32, init=a_init),
        D=param(kg, (Lr, Di), ("layers", "inner"), jnp.float32,
                init=ones_init),
        out_proj=param(kg, (Lr, Di, d), ("layers", "inner", "embed"), dt,
                       stddev=Di ** -0.5),
    )


def mamba1_block(lp: Mamba1Params, x, cfg: ModelConfig, state=None):
    """x: (Bt, S, d).  state=None: full scan (returns y, final_state);
    state=(conv_state, h): single-step decode (S==1)."""
    N, dtr = cfg.ssm_state, cfg.dtr
    h_in = L.rms_norm(lp.norm, x)
    xz = h_in @ lp.in_proj
    xz = constrain(xz, "batch", "seq", "inner")
    x_in, z = jnp.split(xz, 2, axis=-1)

    if state is None:
        x_c = causal_conv1d(x_in, lp.conv_w, lp.conv_b)
        x_c = jax.nn.silu(x_c.astype(jnp.float32)).astype(x.dtype)
        dbc = x_c @ lp.x_proj
        dt_r, B_ssm, C_ssm = jnp.split(dbc, [dtr, dtr + N], axis=-1)
        dt = jax.nn.softplus((dt_r @ lp.dt_w).astype(jnp.float32)
                             + lp.dt_b[None, None])
        A = -jnp.exp(lp.A_log)
        if cfg.use_flash:
            from repro.kernels import ops as kops
            y, h_fin = kops.ssm_scan(
                x_c, dt, A, B_ssm, C_ssm,
                interpret=cfg.use_flash == kops.INTERPRET)
        elif cfg.ssm_time_chunk:
            y, h_fin = selective_scan_chunked(x_c, dt, A, B_ssm, C_ssm,
                                              chunk=cfg.ssm_time_chunk)
        else:
            y, h_fin = selective_scan(x_c, dt, A, B_ssm, C_ssm)
        y = y + lp.D[None, None] * x_c.astype(jnp.float32)
        y = (y.astype(x.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype))
        out = constrain(y @ lp.out_proj, "batch", "seq", "embed")
        W = cfg.conv_width
        conv_tail = jnp.pad(x_in, ((0, 0), (W - 1, 0), (0, 0)))[:, -(W - 1):, :]
        return x + out, (conv_tail, h_fin)

    conv_state, h = state
    x_t, z_t = x_in[:, 0], z[:, 0]
    conv_state, x_c = conv1d_step(conv_state, x_t, lp.conv_w, lp.conv_b)
    x_c = jax.nn.silu(x_c.astype(jnp.float32)).astype(x.dtype)
    dbc = x_c @ lp.x_proj
    dt_r, B_t, C_t = jnp.split(dbc, [dtr, dtr + N], axis=-1)
    dt = jax.nn.softplus((dt_r @ lp.dt_w).astype(jnp.float32) + lp.dt_b[None])
    A = -jnp.exp(lp.A_log)
    h, y = selective_step(h, x_c, dt, A, B_t, C_t)
    y = y + lp.D[None] * x_c.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z_t.astype(jnp.float32)).astype(x.dtype)
    out = y[:, None] @ lp.out_proj
    return x + constrain(out, "batch", None, "embed"), (conv_state, h)


# ---------------------------------------------------------------------------
# Mamba-2 block (zamba2 backbone)
# ---------------------------------------------------------------------------
class Mamba2Params(NamedTuple):
    norm: Param          # (L, d)
    in_proj: Param       # (L, d, 2*Di)
    conv_w: Param        # (L, Di, W)
    conv_b: Param        # (L, Di)
    bc_proj: Param       # (L, d, 2N)
    dt_w: Param          # (L, d, H_ssm)
    dt_b: Param          # (L, H_ssm)
    A_log: Param         # (L, H_ssm)
    D: Param             # (L, Di)
    gate_norm: Param     # (L, Di)
    out_proj: Param      # (L, Di, d)


def init_mamba2(kg: KeyGen, n_layers: int, cfg: ModelConfig) -> Mamba2Params:
    d, Di, N = cfg.d_model, cfg.inner, cfg.ssm_state
    H, W, dt = cfg.n_ssm_heads, cfg.conv_width, cfg.dtype_jnp
    Lr = n_layers
    return Mamba2Params(
        norm=L.init_rmsnorm(kg, Lr, d, dt),
        in_proj=param(kg, (Lr, d, 2 * Di), ("layers", "embed", "inner"), dt,
                      stddev=d ** -0.5),
        conv_w=param(kg, (Lr, Di, W), ("layers", "inner", "conv"), dt,
                     stddev=W ** -0.5),
        conv_b=param(kg, (Lr, Di), ("layers", "inner"), dt, init=zeros_init),
        bc_proj=param(kg, (Lr, d, 2 * N), ("layers", "embed", None), dt,
                      stddev=d ** -0.5),
        dt_w=param(kg, (Lr, d, H), ("layers", "embed", None), dt,
                   stddev=d ** -0.5),
        dt_b=param(kg, (Lr, H), ("layers", None), jnp.float32,
                   init=lambda k, s, _: jnp.log(
                       jnp.expm1(jnp.full(s, 1e-2, jnp.float32)))),
        A_log=param(kg, (Lr, H), ("layers", None), jnp.float32,
                    init=lambda k, s, _: jnp.log(jnp.linspace(1.0, 16.0, s[-1])
                                                 )[None].repeat(s[0], 0)),
        D=param(kg, (Lr, Di), ("layers", "inner"), jnp.float32,
                init=ones_init),
        gate_norm=L.init_rmsnorm(kg, Lr, Di, dt),
        out_proj=param(kg, (Lr, Di, d), ("layers", "inner", "embed"), dt,
                       stddev=Di ** -0.5),
    )


def mamba2_block(lp: Mamba2Params, x, cfg: ModelConfig, state=None):
    """Mamba-2: scalar per-head decay; reuses the mamba1 recurrence with A
    and dt broadcast across each head's channels."""
    N, H, dh = cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_head_dim
    h_in = L.rms_norm(lp.norm, x)
    xz = constrain(h_in @ lp.in_proj, "batch", "seq", "inner")
    x_in, z = jnp.split(xz, 2, axis=-1)
    bc = h_in @ lp.bc_proj
    B_ssm, C_ssm = jnp.split(bc, 2, axis=-1)
    dt_h = jax.nn.softplus((h_in @ lp.dt_w).astype(jnp.float32)
                           + lp.dt_b[None, None])          # (Bt,S,H)
    A_h = -jnp.exp(lp.A_log)                               # (H,)
    A_full = jnp.repeat(A_h, dh)[:, None].repeat(N, 1)     # (Di, N)
    dt_full = jnp.repeat(dt_h, dh, axis=-1)                # (Bt,S,Di)

    if state is None:
        x_c = causal_conv1d(x_in, lp.conv_w, lp.conv_b)
        x_c = jax.nn.silu(x_c.astype(jnp.float32)).astype(x.dtype)
        if cfg.use_flash:
            from repro.kernels import ops as kops
            y, h_fin = kops.ssm_scan(
                x_c, dt_full, A_full, B_ssm, C_ssm,
                interpret=cfg.use_flash == kops.INTERPRET)
        elif cfg.ssm_time_chunk:
            y, h_fin = selective_scan_chunked(x_c, dt_full, A_full, B_ssm,
                                              C_ssm,
                                              chunk=cfg.ssm_time_chunk)
        else:
            y, h_fin = selective_scan(x_c, dt_full, A_full, B_ssm, C_ssm)
        y = y + lp.D[None, None] * x_c.astype(jnp.float32)
        y = L.rms_norm(lp.gate_norm,
                       y.astype(x.dtype) *
                       jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype))
        out = constrain(y @ lp.out_proj, "batch", "seq", "embed")
        W = cfg.conv_width
        conv_tail = jnp.pad(x_in, ((0, 0), (W - 1, 0), (0, 0)))[:, -(W - 1):, :]
        return x + out, (conv_tail, h_fin)

    conv_state, h = state
    x_t, z_t = x_in[:, 0], z[:, 0]
    conv_state, x_c = conv1d_step(conv_state, x_t, lp.conv_w, lp.conv_b)
    x_c = jax.nn.silu(x_c.astype(jnp.float32)).astype(x.dtype)
    h, y = selective_step(h, x_c, dt_full[:, 0], A_full, B_ssm[:, 0],
                          C_ssm[:, 0])
    y = y + lp.D[None] * x_c.astype(jnp.float32)
    y = L.rms_norm(lp.gate_norm,
                   y.astype(x.dtype) *
                   jax.nn.silu(z_t.astype(jnp.float32)).astype(x.dtype))
    out = y[:, None] @ lp.out_proj
    return x + constrain(out, "batch", None, "embed"), (conv_state, h)


# ---------------------------------------------------------------------------
# Falcon-mamba: pure Mamba-1 LM
# ---------------------------------------------------------------------------
class MambaLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.vocab_padded = L.pad_vocab(cfg.vocab)

    def init(self, key):
        cfg = self.cfg
        kg = KeyGen(key)
        dt = cfg.dtype_jnp
        return {
            "embed": L.init_embedding(kg, self.vocab_padded, cfg.d_model, dt),
            "layers": init_mamba1(kg, cfg),
            "final_norm": param(kg, (cfg.d_model,), ("embed",), dt,
                                init=ones_init),
        }

    def hidden_states(self, values, x, with_state=False):
        cfg = self.cfg

        def body(h, lp):
            h2, st = mamba1_block(lp, h, cfg)
            return h2, st if with_state else None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, states = scan_or_unroll(body, x, values["layers"], cfg.scan_layers)
        return L.rms_norm(values["final_norm"], x), states

    def _logits(self, values, h):
        logits = L.logits_head(values["embed"], h).astype(jnp.float32)
        if self.vocab_padded > self.cfg.vocab:
            pad = jnp.arange(self.vocab_padded) >= self.cfg.vocab
            logits = jnp.where(pad[None, None], -1e30, logits)
        return logits

    def loss(self, values, batch):
        x = L.embed(values["embed"], batch["tokens"])
        h, _ = self.hidden_states(values, x)
        nll = L.nll_loss(values["embed"], h, batch["labels"], self.cfg.vocab,
                         self.vocab_padded, self.cfg.ce_seq_chunk)
        return nll, {"nll": nll, "aux": jnp.float32(0.0)}

    def init_cache(self, batch: int, seq_len: int):
        cfg = self.cfg
        Lr, Di, N, W = cfg.num_layers, cfg.inner, cfg.ssm_state, cfg.conv_width
        return {
            "conv": jnp.zeros((Lr, batch, W - 1, Di), cfg.dtype_jnp),
            "ssm": jnp.zeros((Lr, batch, Di, N), jnp.float32),
        }

    def prefill(self, values, batch, seq_len: int):
        x = L.embed(values["embed"], batch["tokens"])
        h, states = self.hidden_states(values, x, with_state=True)
        cache = {"conv": states[0], "ssm": states[1]}
        return self._logits(values, h[:, -1:]), cache

    def decode_step(self, values, cache, tokens, cur_pos):
        cfg = self.cfg
        x = L.embed(values["embed"], tokens)

        def body(h, xs):
            lp, conv, ssm = xs
            h2, (nconv, nssm) = mamba1_block(lp, h, cfg, state=(conv, ssm))
            return h2, (nconv, nssm)

        h, (nconv, nssm) = scan_or_unroll(
            body, x, (values["layers"], cache["conv"], cache["ssm"]),
            cfg.scan_layers)
        h = L.rms_norm(values["final_norm"], h)
        return self._logits(values, h), {"conv": nconv, "ssm": nssm}


# ---------------------------------------------------------------------------
# Zamba2 hybrid: Mamba-2 backbone + weight-tied shared attention block
# ---------------------------------------------------------------------------
class HybridLM:
    """``shared_attn_every`` mamba2 layers are preceded by one application of
    a single weight-tied (attention + MLP) block; each application keeps its
    own KV cache."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.vocab_padded = L.pad_vocab(cfg.vocab)
        k = cfg.shared_attn_every
        self.n_apps = math.ceil(cfg.num_layers / k)
        # group g covers mamba layers [g*k, min((g+1)*k, L))
        self.group_sizes = [min((g + 1) * k, cfg.num_layers) - g * k
                            for g in range(self.n_apps)]

    def init(self, key):
        cfg = self.cfg
        kg = KeyGen(key)
        dt = cfg.dtype_jnp
        shared = {
            "attn_norm": param(kg, (cfg.d_model,), ("embed",), dt,
                               init=ones_init),
            "attn": L.init_attention(kg, 1, cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.hd, dt),
            "mlp_norm": param(kg, (cfg.d_model,), ("embed",), dt,
                              init=ones_init),
            "mlp": L.init_mlp(kg, 1, cfg.d_model, cfg.d_ff, dt),
        }
        return {
            "embed": L.init_embedding(kg, self.vocab_padded, cfg.d_model, dt),
            "layers": init_mamba2(kg, cfg.num_layers, cfg),
            "shared": shared,
            "final_norm": param(kg, (cfg.d_model,), ("embed",), dt,
                                init=ones_init),
        }

    def _shared_slice(self, values):
        sh = values["shared"]
        return {
            "attn_norm": sh["attn_norm"],
            "attn": jax.tree.map(lambda a: a[0], sh["attn"]),
            "mlp_norm": sh["mlp_norm"],
            "mlp": jax.tree.map(lambda a: a[0], sh["mlp"]),
        }

    def _apply_shared_full(self, sh, h):
        cfg = self.cfg
        hn = L.rms_norm(sh["attn_norm"], h)
        h = h + L.full_attention(sh["attn"], None, hn, n_heads=cfg.n_heads,
                                 n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                                 rope_theta=cfg.rope_theta,
                                 use_flash=cfg.use_flash,
                                 q_chunk=cfg.attn_q_chunk)
        hn = L.rms_norm(sh["mlp_norm"], h)
        return h + L.mlp(sh["mlp"], hn)

    def hidden_states(self, values, x):
        cfg = self.cfg
        sh = self._shared_slice(values)

        def mamba_body(h, lp):
            h2, _ = mamba2_block(lp, h, cfg)
            return h2, None

        if cfg.remat:
            mamba_body = jax.checkpoint(mamba_body)
        off = 0
        for g, size in enumerate(self.group_sizes):
            x = self._apply_shared_full(sh, x)
            grp = jax.tree.map(lambda a: a[off:off + size], values["layers"])
            x, _ = scan_or_unroll(mamba_body, x, grp, cfg.scan_layers)
            off += size
        return L.rms_norm(values["final_norm"], x)

    def _logits(self, values, h):
        logits = L.logits_head(values["embed"], h).astype(jnp.float32)
        if self.vocab_padded > self.cfg.vocab:
            pad = jnp.arange(self.vocab_padded) >= self.cfg.vocab
            logits = jnp.where(pad[None, None], -1e30, logits)
        return logits

    def loss(self, values, batch):
        x = L.embed(values["embed"], batch["tokens"])
        h = self.hidden_states(values, x)
        nll = L.nll_loss(values["embed"], h, batch["labels"], self.cfg.vocab,
                         self.vocab_padded, self.cfg.ce_seq_chunk)
        return nll, {"nll": nll, "aux": jnp.float32(0.0)}

    def init_cache(self, batch: int, seq_len: int):
        cfg = self.cfg
        Lr, Di, N, W = cfg.num_layers, cfg.inner, cfg.ssm_state, cfg.conv_width
        one = L.init_kv_cache(batch, seq_len, cfg.n_kv_heads, cfg.hd,
                              cfg.dtype_jnp)
        kv = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (self.n_apps,) + a.shape).copy(), one)
        return {
            "conv": jnp.zeros((Lr, batch, W - 1, Di), cfg.dtype_jnp),
            "ssm": jnp.zeros((Lr, batch, Di, N), jnp.float32),
            "kv": kv,
        }

    def decode_step(self, values, cache, tokens, cur_pos):
        cfg = self.cfg
        sh = self._shared_slice(values)
        x = L.embed(values["embed"], tokens)

        def mamba_body(h, xs):
            lp, conv, ssm = xs
            h2, (nc, ns) = mamba2_block(lp, h, cfg, state=(conv, ssm))
            return h2, (nc, ns)

        new_conv, new_ssm, new_kv = [], [], []
        off = 0
        for g, size in enumerate(self.group_sizes):
            kv_g = jax.tree.map(lambda a: a[g], cache["kv"])
            hn = L.rms_norm(sh["attn_norm"], x)
            a_out, nkv = L.decode_attention(
                sh["attn"], hn, kv_g, cur_pos, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                rope_theta=cfg.rope_theta)
            x = x + a_out
            hn = L.rms_norm(sh["mlp_norm"], x)
            x = x + L.mlp(sh["mlp"], hn)
            new_kv.append(nkv)

            grp = jax.tree.map(lambda a: a[off:off + size], values["layers"])
            conv_g = cache["conv"][off:off + size]
            ssm_g = cache["ssm"][off:off + size]
            x, (nc, ns) = scan_or_unroll(mamba_body, x,
                                         (grp, conv_g, ssm_g),
                                         cfg.scan_layers)
            new_conv.append(nc)
            new_ssm.append(ns)
            off += size

        h = L.rms_norm(values["final_norm"], x)
        cache_out = {
            "conv": jnp.concatenate(new_conv, axis=0),
            "ssm": jnp.concatenate(new_ssm, axis=0),
            "kv": jax.tree.map(lambda *xs: jnp.stack(xs), *new_kv),
        }
        return self._logits(values, h), cache_out

    def prefill(self, values, batch, seq_len: int):
        """Full-sequence pass that fills SSM + KV caches."""
        cfg = self.cfg
        sh = self._shared_slice(values)
        x = L.embed(values["embed"], batch["tokens"])
        B = x.shape[0]

        def mamba_body(h, lp):
            h2, st = mamba2_block(lp, h, cfg)
            return h2, st

        convs, ssms, kvs = [], [], []
        off = 0
        for g, size in enumerate(self.group_sizes):
            hn = L.rms_norm(sh["attn_norm"], x)
            a_out, kv = L.prefill_attention(
                sh["attn"], hn, seq_len, n_heads=cfg.n_heads,
                n_kv=cfg.n_kv_heads, head_dim=cfg.hd,
                rope_theta=cfg.rope_theta, q_chunk=cfg.attn_q_chunk)
            x = x + a_out
            hn = L.rms_norm(sh["mlp_norm"], x)
            x = x + L.mlp(sh["mlp"], hn)
            kvs.append(kv)
            grp = jax.tree.map(lambda a: a[off:off + size], values["layers"])
            x, (nc, ns) = scan_or_unroll(mamba_body, x, grp,
                                         cfg.scan_layers)
            convs.append(nc)
            ssms.append(ns)
            off += size

        h = L.rms_norm(values["final_norm"], x[:, -1:])
        cache = {
            "conv": jnp.concatenate(convs, axis=0),
            "ssm": jnp.concatenate(ssms, axis=0),
            "kv": jax.tree.map(lambda *xs: jnp.stack(xs), *kvs),
        }
        return self._logits(values, h), cache
