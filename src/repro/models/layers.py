"""Shared neural building blocks for the 10 assigned architectures.

Pure-functional JAX: every layer is ``apply(params_values, x, ...)`` where
params were created by the matching ``init_*`` (stacked over layers by the
callers).  Activation shardings are annotated with logical axis names via
``repro.dist.constrain`` — no-ops without an active mesh, so the exact same
code runs 1-device smoke tests and the 512-device dry-run.

Attention supports:  GQA (n_kv_heads < n_heads), RoPE, causal masking,
sliding windows (danube/zamba long-context), cross-attention (seamless),
a unified ring-buffer KV cache for decode (full-attention caches are a ring
of capacity seq_len; SWA caches a ring of capacity window), and an optional
Pallas flash-attention path for TPU.

MoE implements per-group capacity routing with sort-free scatter dispatch
(positions via one-hot cumsum), so compiled HLO FLOPs reflect real expert
work instead of dense dispatch einsums.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models.module import KeyGen, Param, param, ones_init, zeros_init


# ---------------------------------------------------------------------------
# norms / rotary
# ---------------------------------------------------------------------------
def init_rmsnorm(kg: KeyGen, layers: int, dim: int, dtype):
    return param(kg, (layers, dim), ("layers", "embed"), dtype, init=ones_init)


def rms_norm(scale, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
class AttnParams(NamedTuple):
    wq: Param      # (L, d, H*hd)
    wk: Param      # (L, d, Kh*hd)
    wv: Param      # (L, d, Kh*hd)
    wo: Param      # (L, H*hd, d)


def init_attention(kg: KeyGen, layers: int, d_model: int, n_heads: int,
                   n_kv: int, head_dim: int, dtype) -> AttnParams:
    std = d_model ** -0.5
    return AttnParams(
        wq=param(kg, (layers, d_model, n_heads * head_dim),
                 ("layers", "embed", "qkv"), dtype, stddev=std),
        wk=param(kg, (layers, d_model, n_kv * head_dim),
                 ("layers", "embed", "qkv"), dtype, stddev=std),
        wv=param(kg, (layers, d_model, n_kv * head_dim),
                 ("layers", "embed", "qkv"), dtype, stddev=std),
        wo=param(kg, (layers, n_heads * head_dim, d_model),
                 ("layers", "qkv", "embed"), dtype, stddev=std),
    )


class KVCache(NamedTuple):
    """Unified ring-buffer cache: capacity C = seq_len (full attention)
    or window (SWA).  ``pos`` holds the absolute position stored in each
    slot (-1 = empty); masking uses positions, so full and windowed caches
    share one code path."""
    k: jnp.ndarray        # (B, C, Kh, hd)
    v: jnp.ndarray        # (B, C, Kh, hd)
    pos: jnp.ndarray      # (B, C) int32


def init_kv_cache(batch: int, capacity: int, n_kv: int, head_dim: int,
                  dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
    )


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def attention_scores(q, k, mask, dtype):
    """q: (B,Sq,H,hd), k: (B,Sk,Kh,hd) -> ctx weights (B,H,Sq,Sk) given
    additive-mask ``mask`` broadcastable to (B, 1|H, Sq, Sk)."""
    B, Sq, H, hd = q.shape
    Kh = k.shape[2]
    group = H // Kh
    qg = q.reshape(B, Sq, Kh, group, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    logits = logits.reshape(B, Kh * group, Sq, -1)
    logits = logits + mask
    w = jax.nn.softmax(logits, axis=-1)
    return w.astype(dtype)


def attention_context(w, v):
    """w: (B,H,Sq,Sk), v: (B,Sk,Kh,hd) -> (B,Sq,H,hd)."""
    B, H, Sq, Sk = w.shape
    Kh = v.shape[2]
    group = H // Kh
    wg = w.reshape(B, Kh, group, Sq, Sk)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", wg.astype(jnp.float32),
                     v.astype(jnp.float32))
    return ctx.reshape(B, Sq, H, -1)


def causal_mask(sq: int, sk: int, window: int = 0,
                q_offset: int = 0) -> jnp.ndarray:
    """Additive (1, 1, Sq, Sk) mask.  window=0 -> plain causal."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    ok = kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, -1e30)[None, None]


def _chunked_attention(q, k, v, *, causal, window, q_chunk, dtype):
    """Exact attention with the query axis processed in chunks.

    Row-wise softmax is independent across queries, so per-chunk full-row
    softmax is exact (no online rescaling needed) while bounding the score
    buffer to (B, H, q_chunk, Sk).  With a sliding window the KV range per
    chunk is statically sliced to q_chunk + window columns, making SWA
    prefill/train linear in S.  Each chunk is rematerialized so the
    backward pass never stores a full (Sq, Sk) score tensor.
    """
    B, S, H, D = q.shape
    n = S // q_chunk
    use_kv_slice = bool(window) and window + q_chunk < S

    def one_chunk(i):
        q_i = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        if use_kv_slice:
            kv_len = q_chunk + window
            start = jnp.clip(i * q_chunk - window, 0, S - kv_len)
            k_i = jax.lax.dynamic_slice_in_dim(k, start, kv_len, axis=1)
            v_i = jax.lax.dynamic_slice_in_dim(v, start, kv_len, axis=1)
            kpos = start + jnp.arange(kv_len)[None, :]
        else:
            k_i, v_i = k, v
            kpos = jnp.arange(k.shape[1])[None, :]
        qpos = i * q_chunk + jnp.arange(q_chunk)[:, None]
        ok = jnp.ones((q_chunk, kpos.shape[1]), bool)
        if causal:
            ok &= kpos <= qpos
        if window:
            ok &= kpos > qpos - window
        mask = jnp.where(ok, 0.0, -1e30)[None, None]
        w = attention_scores(q_i, k_i, mask, dtype)
        return attention_context(w, v_i).astype(dtype)

    chunks = jax.lax.map(jax.checkpoint(one_chunk), jnp.arange(n))
    return jnp.swapaxes(chunks, 0, 1).reshape(B, S, H, D)


def full_attention(p: AttnParams, li, x, *, n_heads, n_kv, head_dim,
                   rope_theta, window=0, positions=None, use_flash=False,
                   causal=True, q_chunk=0):
    """Training/prefill self-attention over the full sequence.

    ``q_chunk`` > 0 and S > 2*q_chunk routes through exact chunked
    attention (memory O(S * q_chunk) instead of O(S^2)); ``use_flash``
    routes through the Pallas kernel instead: compiled with Mosaic (TPU),
    or in the Pallas interpreter when it is ``kernels.ops.INTERPRET``.
    ``li`` is unused
    (params come pre-sliced by the layer scan)."""
    wq, wk, wv, wo = p
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = _split_heads(x @ wq, n_heads, head_dim)
    k = _split_heads(x @ wk, n_kv, head_dim)
    v = _split_heads(x @ wv, n_kv, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = constrain(q, "attn_batch", "seq", "heads", "head_dim")
    k = constrain(k, "attn_batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "attn_batch", "seq", "kv_heads", "head_dim")
    if use_flash:
        from repro.kernels import ops as kops
        ctx = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   interpret=use_flash == kops.INTERPRET)
    elif q_chunk and S > 2 * q_chunk and S % q_chunk == 0:
        ctx = _chunked_attention(q, k, v, causal=causal, window=window,
                                 q_chunk=q_chunk, dtype=x.dtype)
    else:
        mask = causal_mask(S, S, window) if causal else \
            jnp.zeros((1, 1, 1, S))
        w = attention_scores(q, k, mask, x.dtype)
        ctx = attention_context(w, v).astype(x.dtype)
    ctx = constrain(ctx, "batch", "seq", "heads", "head_dim")
    out = ctx.reshape(B, S, n_heads * head_dim) @ wo
    return constrain(out, "batch", "seq", "embed")


def prefill_attention(p: AttnParams, x, capacity: int, *, n_heads, n_kv,
                      head_dim, rope_theta, window=0, q_chunk=0):
    """Full-sequence attention that also fills a fresh KV cache (ring
    layout, capacity ``capacity``)."""
    wq, wk, wv, wo = p
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q = _split_heads(x @ wq, n_heads, head_dim)
    k = _split_heads(x @ wk, n_kv, head_dim)
    v = _split_heads(x @ wv, n_kv, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    if q_chunk and S > 2 * q_chunk and S % q_chunk == 0:
        ctx = _chunked_attention(q, k, v, causal=True, window=window,
                                 q_chunk=q_chunk, dtype=x.dtype)
    else:
        mask = causal_mask(S, S, window)
        w = attention_scores(q, k, mask, x.dtype)
        ctx = attention_context(w, v).astype(x.dtype)
    out = ctx.reshape(B, S, n_heads * head_dim) @ wo

    C = capacity
    if S >= C:
        # keep the last C entries
        kc, vc = k[:, S - C:], v[:, S - C:]
        pc = jnp.broadcast_to(jnp.arange(S - C, S, dtype=jnp.int32)[None],
                              (B, C))
        # ring alignment: entry at slot (pos % C)
        slots = pc[0] % C
        order = jnp.argsort(slots)
        new = KVCache(kc[:, order], vc[:, order], pc[:, order])
    else:
        pad = C - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pc = jnp.concatenate([
            jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)),
            jnp.full((B, pad), -1, jnp.int32)], axis=1)
        new = KVCache(kc, vc, pc)
    return constrain(out, "batch", "seq", "embed"), new


def decode_attention(p: AttnParams, x, cache: KVCache, cur_pos, *, n_heads,
                     n_kv, head_dim, rope_theta, window=0):
    """One-token decode: write (k,v) at slot cur_pos % C, attend over cache.

    x: (B, 1, d); cur_pos: scalar int32 (same position across batch)."""
    wq, wk, wv, wo = p
    B = x.shape[0]
    C = cache.k.shape[1]
    pos_b = jnp.full((B, 1), cur_pos, jnp.int32)
    q = _split_heads(x @ wq, n_heads, head_dim)
    k = _split_heads(x @ wk, n_kv, head_dim)
    v = _split_heads(x @ wv, n_kv, head_dim)
    q = apply_rope(q, pos_b, rope_theta)
    k = apply_rope(k, pos_b, rope_theta)

    slot = jnp.mod(cur_pos, C)
    ck = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
    cp = jax.lax.dynamic_update_slice(cache.pos, pos_b, (0, slot))
    ck = constrain(ck, "batch", "kv_seq", "kv_heads", "head_dim")
    cv = constrain(cv, "batch", "kv_seq", "kv_heads", "head_dim")

    valid = (cp >= 0) & (cp <= cur_pos)
    if window:
        valid &= cp > cur_pos - window
    mask = jnp.where(valid, 0.0, -1e30)[:, None, None, :]   # (B,1,1,C)
    w = attention_scores(q, ck, mask, x.dtype)
    ctx = attention_context(w, cv).astype(x.dtype)
    out = ctx.reshape(B, 1, n_heads * head_dim) @ wo
    out = constrain(out, "batch", None, "embed")
    return out, KVCache(ck, cv, cp)


def cross_attention(p: AttnParams, x, enc_kv, *, n_heads, n_kv, head_dim):
    """Decoder->encoder cross attention (no rope, no mask over enc)."""
    wq, wk, wv, wo = p
    B, S, _ = x.shape
    q = _split_heads(x @ wq, n_heads, head_dim)
    k, v = enc_kv                                # precomputed (B, Se, Kh, hd)
    mask = jnp.zeros((1, 1, 1, k.shape[1]))
    w = attention_scores(q, k, mask, x.dtype)
    ctx = attention_context(w, v).astype(x.dtype)
    out = ctx.reshape(B, S, n_heads * head_dim) @ wo
    return constrain(out, "batch", "seq", "embed")


def encode_cross_kv(p: AttnParams, enc_out, *, n_kv, head_dim):
    k = _split_heads(enc_out @ p.wk, n_kv, head_dim)
    v = _split_heads(enc_out @ p.wv, n_kv, head_dim)
    return (k, v)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------
class MlpParams(NamedTuple):
    w_gate: Param    # (L, d, ff)
    w_up: Param      # (L, d, ff)
    w_down: Param    # (L, ff, d)


def init_mlp(kg: KeyGen, layers: int, d_model: int, d_ff: int, dtype) -> MlpParams:
    return MlpParams(
        w_gate=param(kg, (layers, d_model, d_ff), ("layers", "embed", "mlp"),
                     dtype, stddev=d_model ** -0.5),
        w_up=param(kg, (layers, d_model, d_ff), ("layers", "embed", "mlp"),
                   dtype, stddev=d_model ** -0.5),
        w_down=param(kg, (layers, d_ff, d_model), ("layers", "mlp", "embed"),
                     dtype, stddev=d_ff ** -0.5),
    )


def mlp(p: MlpParams, x):
    w_gate, w_up, w_down = p
    h = jax.nn.silu((x @ w_gate).astype(jnp.float32)).astype(x.dtype) * (x @ w_up)
    h = constrain(h, "batch", "seq", "mlp")
    return constrain(h @ w_down, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Mixture of Experts (routed top-k, per-group capacity, scatter dispatch)
# ---------------------------------------------------------------------------
class MoeParams(NamedTuple):
    w_router: Param      # (L, d, E)
    w_gate: Param        # (L, E, d, ff)
    w_up: Param          # (L, E, d, ff)
    w_down: Param        # (L, E, ff, d)
    shared: Optional[MlpParams]   # shared experts as one fused MLP


def init_moe(kg: KeyGen, layers: int, d_model: int, n_experts: int,
             expert_ff: int, n_shared: int, dtype,
             pad_experts_to: int = 0) -> MoeParams:
    E = max(n_experts, pad_experts_to)
    std = d_model ** -0.5
    shared = None
    if n_shared:
        shared = init_mlp(kg, layers, d_model, n_shared * expert_ff, dtype)
    return MoeParams(
        w_router=param(kg, (layers, d_model, E), ("layers", "embed", None),
                       jnp.float32, stddev=std),
        w_gate=param(kg, (layers, E, d_model, expert_ff),
                     ("layers", "expert", "embed", "expert_mlp"), dtype, stddev=std),
        w_up=param(kg, (layers, E, d_model, expert_ff),
                   ("layers", "expert", "embed", "expert_mlp"), dtype, stddev=std),
        w_down=param(kg, (layers, E, expert_ff, d_model),
                     ("layers", "expert", "expert_mlp", "embed"), dtype,
                     stddev=expert_ff ** -0.5),
    shared=shared)


def moe(p: MoeParams, x, *, n_experts: int, top_k: int,
        capacity_factor: float = 1.25, group_tokens: bool = False):
    """Routed MoE.  x: (B, S, d) -> (y, aux_loss).

    Routing groups are batch rows; with ``group_tokens`` (decode
    optimization) the whole (B*S) token stream forms one routing group so
    expert capacity reflects the true token count instead of per-row
    worst case (an EP all-to-all moves tokens across the batch shards).
    Only the first ``n_experts`` experts are routable (padding experts for
    mesh divisibility receive -inf router logits).
    """
    w_router, w_gate, w_up, w_down, shared = p
    B, S, d = x.shape
    E = w_gate.shape[0]
    xg = x.reshape(1, B * S, d) if group_tokens else x
    G, T = xg.shape[0], xg.shape[1]

    logits = (xg.astype(jnp.float32) @ w_router)          # (G, T, E)
    if E > n_experts:
        pad_mask = jnp.arange(E) >= n_experts
        logits = jnp.where(pad_mask[None, None], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)            # (G, T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style): E * sum(f_e * p_e)
    me = probs.mean(axis=(0, 1))
    ce = jax.nn.one_hot(top_e, E).sum(2).mean(axis=(0, 1)) / top_k
    aux = n_experts * jnp.sum(me * ce)

    C = max(1, math.ceil(T * top_k * capacity_factor / n_experts))
    e_flat = top_e.reshape(G, T * top_k)                  # (G, TK)
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)       # (G, TK, E)
    pos = jnp.cumsum(oh, axis=1) - oh                     # (G, TK, E)
    pos_sel = jnp.take_along_axis(pos, e_flat[..., None], -1)[..., 0]
    keep = pos_sel < C                                    # (G, TK)
    pos_cl = jnp.minimum(pos_sel, C - 1)

    x_rep = jnp.repeat(xg, top_k, axis=1)                 # (G, TK, d)

    def scatter_row(xr, er, pr, kr):
        buf = jnp.zeros((E, C, d), xr.dtype)
        return buf.at[er, pr].add(xr * kr[:, None].astype(xr.dtype))

    buf = jax.vmap(scatter_row)(x_rep, e_flat, pos_cl, keep)  # (G, E, C, d)
    buf = constrain(buf, "batch", "expert", None, None)

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, w_gate,
                               preferred_element_type=jnp.float32))
    h = (h.astype(x.dtype) * jnp.einsum("gecd,edf->gecf", buf, w_up))
    h = constrain(h, "batch", "expert", None, "expert_mlp")
    y_buf = jnp.einsum("gecf,efd->gecd", h, w_down)
    y_buf = constrain(y_buf, "batch", "expert", None, None)

    def gather_row(yb, er, pr, kr):
        return yb[er, pr] * kr[:, None].astype(yb.dtype)

    y_tok = jax.vmap(gather_row)(y_buf, e_flat, pos_cl, keep)  # (G, TK, d)
    y = (y_tok.reshape(G, T, top_k, d)
         * top_p[..., None].astype(y_tok.dtype)).sum(axis=2)
    y = y.reshape(B, S, d)
    if shared is not None:
        y = y + mlp(shared, x)
    return constrain(y, "batch", "seq", "embed"), aux


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------
def init_embedding(kg: KeyGen, vocab: int, d_model: int, dtype):
    return param(kg, (vocab, d_model), ("vocab", "embed"), dtype)


def embed(table, tokens):
    out = jnp.take(table, tokens, axis=0)
    return constrain(out, "batch", "seq", "embed")


def logits_head(table, x):
    """Tied LM head: (B, S, d) @ (V, d)^T -> (B, S, V)."""
    out = jnp.einsum("bsd,vd->bsv", x, table)
    return constrain(out, "batch", "seq", "vocab")


def pad_vocab(vocab: int, multiple: int = 128) -> int:
    return int(math.ceil(vocab / multiple) * multiple)


def nll_loss(table, h, labels, vocab: int, vocab_padded: int,
             seq_chunk: int = 0):
    """Next-token NLL.  With ``seq_chunk`` > 0 the (B, S, V) logits are
    never materialized: the sequence is processed in chunks, each chunk's
    logits/log-softmax live only inside a rematerialized map step — HBM
    traffic drops from O(B*S*V) to O(B*seq_chunk*V) per step (the
    'fused cross-entropy' memory optimization, see EXPERIMENTS §Perf)."""
    B, S, d = h.shape
    pad_mask = (jnp.arange(vocab_padded) >= vocab) if vocab_padded > vocab \
        else None

    def chunk_nll(h_i, lab_i):
        logits = logits_head(table, h_i).astype(jnp.float32)
        if pad_mask is not None:
            logits = jnp.where(pad_mask[None, None], -1e30, logits)
        lp = jax.nn.log_softmax(logits, axis=-1)
        tgt = jnp.take_along_axis(lp, lab_i[..., None], axis=-1)[..., 0]
        mask = (lab_i >= 0).astype(jnp.float32)
        return (tgt * mask).sum(), mask.sum()

    if seq_chunk and S > seq_chunk and S % seq_chunk == 0:
        def one(i):
            h_i = jax.lax.dynamic_slice_in_dim(h, i * seq_chunk, seq_chunk, 1)
            lab_i = jax.lax.dynamic_slice_in_dim(labels, i * seq_chunk,
                                                 seq_chunk, 1)
            return chunk_nll(h_i, lab_i)
        tot, cnt = jax.lax.map(jax.checkpoint(one),
                               jnp.arange(S // seq_chunk))
        return -tot.sum() / jnp.maximum(cnt.sum(), 1.0)
    tot, cnt = chunk_nll(h, labels)
    return -tot / jnp.maximum(cnt, 1.0)
