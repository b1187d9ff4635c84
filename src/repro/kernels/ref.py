"""Pure-jnp oracles for every Pallas kernel.

Each `*_ref` is the numerical ground truth the kernels are validated against
(tests sweep shapes/dtypes with assert_allclose).  They are written for
clarity, not speed, and always follow the paper's precision rules:
fp32 softmax/statistics, fp32 GEMM accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.epilogue import LN_EPS, RMS_EPS

NEG_INF = -1e30


def _dot(a, b, accum=jnp.float32):
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=accum)


def matmul_ref(a, b, *, activation: str = "none", gate=None,
               accum_dtype=jnp.float32, out_dtype=None):
    """C = act(A @ B); the dot emits `out_dtype` directly (MXU accumulates
    fp32 internally; a narrow output keeps the backward dots narrow too) and
    the activation epilogue runs in fp32 (paper T6)."""
    out_dtype = out_dtype or a.dtype
    if activation == "none":
        return _dot(a, b, out_dtype)
    c = _dot(a, b, out_dtype).astype(jnp.float32)
    if activation == "gelu":
        c = jax.nn.gelu(c, approximate=True)
    elif activation == "silu":
        c = jax.nn.silu(c)
    elif activation == "swiglu":
        assert gate is not None
        c = jax.nn.silu(c) * gate.astype(jnp.float32)
    else:
        raise ValueError(activation)
    return c.astype(out_dtype)


def _attn_mask(q_len, kv_len, *, causal, window, q_offset=0):
    """Boolean mask [q_len, kv_len]: True = attend.

    `q_offset`: absolute position of q row 0 (for chunked/seq-sharded Q —
    the key positions are 0..kv_len-1)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window and window > 0:
        mask &= k_pos > q_pos - window
    return mask


def attention_ref(q, k, v, *, causal=True, window=0, q_offset=0,
                  softmax_dtype=jnp.float32, out_dtype=None, scale=None):
    """Naive full-materialization attention (the paper's baseline).

    q: [B, Sq, H, D]; k, v: [B, Skv, KV, D] with GQA (H % KV == 0).
    Softmax in fp32 regardless of input dtype (paper invariant).
    """
    out_dtype = out_dtype or q.dtype
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qf = q.astype(softmax_dtype).reshape(B, Sq, KV, G, D)
    kf = k.astype(softmax_dtype)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) * scale
    mask = _attn_mask(Sq, Skv, causal=causal, window=window, q_offset=q_offset)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(softmax_dtype))
    return out.reshape(B, Sq, H, D).astype(out_dtype)


def flash_attention_ref(q, k, v, *, causal=True, window=0, q_offset=0,
                        block_kv=128, out_dtype=None):
    """Online-softmax (FlashAttention-2 dataflow) oracle: iterates KV blocks
    with running (m, l, o) statistics in fp32.  The Q.K^T and P.V GEMMs run
    in the *operand* dtype with fp32 accumulation (paper T6: low-precision
    GEMMs, fp32 softmax) — this is also what makes the dry-run's lowered
    FLOPs land on the bf16 MXU peak instead of the fp32 one."""
    out_dtype = out_dtype or q.dtype
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qr = q.reshape(B, Sq, KV, G, D)

    n_blocks = (Skv + block_kv - 1) // block_kv
    pad = n_blocks * block_kv - Skv
    kf = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kpos = jnp.arange(n_blocks * block_kv)
    valid = kpos < Skv

    qpos = jnp.arange(Sq) + q_offset

    def body(carry, blk):
        m, l, o = carry
        kb, vb, pos_b, val_b = blk
        # scores emitted in the operand dtype (the paper converts at the
        # Q.K^T GEMM *output*), upcast to fp32 for the softmax statistics —
        # this also keeps the dq/dk backward dots in the narrow dtype
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kb,
                       preferred_element_type=q.dtype
                       ).astype(jnp.float32) * scale
        msk = val_b[None, :]
        if causal:
            msk = msk & (pos_b[None, :] <= qpos[:, None])
        if window and window > 0:
            msk = msk & (pos_b[None, :] > qpos[:, None] - window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(q.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    o0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    kb = kf.reshape(B, n_blocks, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    vb = vf.reshape(B, n_blocks, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    pos_b = kpos.reshape(n_blocks, block_kv)
    val_b = valid.reshape(n_blocks, block_kv)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), (kb, vb, pos_b, val_b))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return out.astype(out_dtype)


def decode_attention_ref(q, k_cache, v_cache, length, *, window=0,
                         out_dtype=None):
    """Single-token decode oracle.  q: [B, H, D]; caches: [B, S, KV, D];
    `length`: number of valid cache entries (scalar or [B]).  Entries at
    positions >= length are masked.  `window`: only the last `window`
    positions attend (SWA)."""
    out_dtype = out_dtype or q.dtype
    B, S, KV, D = k_cache.shape
    H = q.shape[1]
    G = H // KV
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, G, D)
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, kf)
    pos = jnp.arange(S)[None, :]
    length = jnp.asarray(length)
    ln = length[:, None] if length.ndim else length[None, None]
    msk = pos < ln
    if window and window > 0:
        msk = msk & (pos >= ln - window)
    s = jnp.where(msk[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, D).astype(out_dtype)


def _paged_gather(k_pool, v_pool, block_tables, lengths, head_dim: int,
                  k_scale=None, v_scale=None):
    """Dereference block tables into a dense [B, MB*BS, KV, D] view plus a
    [B, MB*BS] validity mask (token t of entry e = absolute position
    e*BS + t; entries < 0 are absent).  Pools are one layer's
    [NB, BS, KV*D], as stored (launch/steps.cache_layout): the gather comes
    first, so only the gathered rows are split into heads.

    `k_scale`/`v_scale` ([NB, KV] fp32): per-block-per-head dequant scales
    for int8 pools — the single dequant hook for the quantized paged KV
    path (the Pallas kernel applies the same scalar per grid step)."""
    _, BS, KVD = k_pool.shape
    D = head_dim
    KV = KVD // D
    B, MB = block_tables.shape
    present = block_tables >= 0                                  # [B, MB]
    tab = jnp.where(present, block_tables, 0)
    k = k_pool[tab].reshape(B, MB, BS, KV, D).astype(jnp.float32)
    v = v_pool[tab].reshape(B, MB, BS, KV, D).astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[tab][:, :, None, :, None]
    if v_scale is not None:
        v = v * v_scale[tab][:, :, None, :, None]
    k = k.reshape(B, MB * BS, KV, D)
    v = v.reshape(B, MB * BS, KV, D)
    pos = jnp.arange(MB * BS)[None, :]                           # absolute
    msk = pos < jnp.asarray(lengths, jnp.int32)[:, None]
    msk &= jnp.repeat(present, BS, axis=1)
    return k, v, msk


def _paged_scores(q, k, msk):
    """Masked fp32 scores [B, KV, G, S] from q [B, H, D]."""
    B, H, D = q.shape
    KV = k.shape[2]
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, H // KV, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k)
    return jnp.where(msk[:, None, None], s, NEG_INF)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths, *,
                               k_scale=None, v_scale=None, out_dtype=None):
    """Paged single-token decode oracle (block-paged KV cache).

    q: [B, H, D]; k/v_pool: [NB, BS, KV*D] — one layer's global pool of
    fixed-size KV blocks, each token row holding every head; block_tables: [B, MB] int32 block ids per slot in sequence
    order (entries < 0 are absent: unallocated, or owned by another cache
    shard); lengths: [B] valid tokens per slot.  Gathers the table into a
    dense cache and defers to the dense softmax — ground truth, not fast."""
    out_dtype = out_dtype or q.dtype
    B, H, D = q.shape
    k, v, msk = _paged_gather(k_pool, v_pool, block_tables, lengths, D,
                              k_scale, v_scale)
    s = _paged_scores(q, k, msk)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.reshape(B, H, D).astype(out_dtype)


def paged_decode_partials_ref(q, k_pool, v_pool, block_tables, lengths, *,
                              k_scale=None, v_scale=None):
    """Paged decode oracle emitting unnormalized online-softmax partials
    -> (o [B, H, D] fp32, m [B, H], l [B, H]) for the cross-shard T4 merge
    (each shard passes its local pool; absent entries are masked)."""
    B, H, D = q.shape
    k, v, msk = _paged_gather(k_pool, v_pool, block_tables, lengths, D,
                              k_scale, v_scale)
    s = _paged_scores(q, k, msk)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return o.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)


def paged_chunk_partials_ref(q, k_pool, v_pool, block_tables, q_pos,
                             lengths, *, k_scale=None, v_scale=None,
                             tree_mask=None):
    """Chunked-prefill partials: C query tokens per row against the paged
    pool (which already holds this chunk's own KV rows), causal-masked per
    query position.

    q: [B, C, H, D]; k/v_pool: [NB, BS, KV*D]; block_tables: [B, MB]
    (entries < 0 absent); q_pos: [B, C] absolute position of each query
    (pad queries may point past `lengths` — their outputs are garbage the
    caller discards); lengths: [B] valid tokens incl. this chunk.
    -> (o [B, C, H, D] fp32 unnormalized, m [B, C, H], l [B, C, H]) for the
    cross-shard T4 merge, same contract as `paged_decode_partials_ref`.

    tree_mask: optional [B, C, C] bool ancestor matrix for tree-speculative
    verify.  The chunk's C entries then form a token tree scattered at
    positions q_pos (= pos0 + node index): query node i attends the
    committed prefix (< pos0) plus in-chunk node j iff tree_mask[b, i, j].
    A lower-triangular tree_mask reproduces the causal `pos <= q_pos` mask
    exactly (the degenerate single-branch chain)."""
    B, C, H, D = q.shape
    k, v, msk = _paged_gather(k_pool, v_pool, block_tables, lengths, D,
                              k_scale, v_scale)
    KV = k.shape[2]
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(B, C, KV, H // KV, D)
    s = jnp.einsum("bckgd,bskd->bckgs", qf, k)                # [B,C,KV,G,S]
    pos = jnp.arange(k.shape[1])[None, None, :]
    if tree_mask is not None:
        pos0 = q_pos[:, :1]                                   # [B, 1]
        s_pos = pos[0]                                        # [1, S]
        prefix = s_pos < pos0                                 # [B, S]
        in_chunk = (s_pos >= pos0) & (s_pos < pos0 + C)
        rel = jnp.clip(s_pos - pos0, 0, C - 1)                # [B, S]
        anc = jnp.take_along_axis(
            tree_mask, jnp.broadcast_to(rel[:, None, :],
                                        (B, C, s_pos.shape[1])), axis=2)
        keep = msk[:, None, :] & (prefix[:, None, :]
                                  | (in_chunk[:, None, :] & anc))
    else:
        keep = msk[:, None, :] & (pos <= q_pos[:, :, None])   # [B, C, S]
    s = jnp.where(keep[:, :, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bckgs,bskd->bckgd", p, v)
    return (o.reshape(B, C, H, D), m.reshape(B, C, H),
            l.reshape(B, C, H))


def rmsnorm_ref(x, gamma, *, eps=RMS_EPS, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    return y.astype(out_dtype)


def layernorm_ref(x, gamma, beta, *, eps=LN_EPS, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(out_dtype)


def softmax_ref(x, *, axis=-1, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    return jax.nn.softmax(x.astype(jnp.float32), axis=axis).astype(out_dtype)


def ssd_ref(x, dt, A, B, C, D, *, out_dtype=None):
    """Mamba2 SSD oracle — sequential recurrence over time (ground truth).

    x:  [Bt, S, H, P]   (P = head dim)
    dt: [Bt, S, H]      (positive step sizes; pre-softplus'd)
    A:  [H]             (negative decay rates)
    B:  [Bt, S, N]      (input gate,  N = state dim)
    C:  [Bt, S, N]      (output gate)
    D:  [H]             (skip)
    state h: [Bt, H, P, N];  h_t = exp(dt*A) h_{t-1} + dt * x_t B_t^T
                            y_t = h_t C_t + D * x_t
    """
    out_dtype = out_dtype or x.dtype
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af, Bf, Cf, Df = (t.astype(jnp.float32) for t in (A, B, C, D))

    def step(h, inp):
        xt, dtt, bt, ct = inp            # [Bt,H,P], [Bt,H], [Bt,N], [Bt,N]
        decay = jnp.exp(dtt * Af[None])  # [Bt, H]
        h = h * decay[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt)
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    xs = (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
          Bf.transpose(1, 0, 2), Cf.transpose(1, 0, 2))
    hT, ys = jax.lax.scan(step, h0, xs)
    y = ys.transpose(1, 0, 2, 3) + Df[None, None, :, None] * xf
    return y.astype(out_dtype), hT.astype(jnp.float32)


def ssd_chunked_ref(x, dt, A, B, C, D, *, chunk=64, h0=None, out_dtype=None):
    """Chunk-parallel SSD (the state-space-duality form the kernel uses):
    intra-chunk attention-like matmuls + inter-chunk state recurrence.
    Matches ssd_ref up to fp reordering."""
    out_dtype = out_dtype or x.dtype
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    xf = x.astype(jnp.float32).reshape(Bt, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bt, nc, chunk, H)
    Bf = B.astype(jnp.float32).reshape(Bt, nc, chunk, N)
    Cf = C.astype(jnp.float32).reshape(Bt, nc, chunk, N)
    Af = A.astype(jnp.float32)

    # cumulative log-decay within each chunk: a[t] = sum_{u<=t} dt_u * A
    da = dtf * Af[None, None, None, :]            # [Bt,nc,L,H]
    cum = jnp.cumsum(da, axis=2)                  # inclusive
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [Bt,nc,L,L,H] t>=s
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay_mat = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)

    # intra-chunk (the "attention-like" quadratic term)
    g = jnp.einsum("bcln,bcmn->bclm", Cf, Bf)     # [Bt,nc,L,L]
    m = g[..., None] * decay_mat                  # [Bt,nc,L,L,H]
    y_intra = jnp.einsum("bclmh,bcmh,bcmhp->bclhp", m, dtf, xf)

    # chunk-boundary states
    chunk_decay = jnp.exp(cum[:, :, -1])          # [Bt,nc,H]
    b_decay = jnp.exp(cum[:, :, -1:, :] - cum)    # decay from t to chunk end
    states = jnp.einsum("bclh,bclh,bclhp,bcln->bchpn",
                        b_decay, dtf, xf, Bf)     # [Bt,nc,H,P,N]

    def scan_fn(h, inp):
        st, dec = inp
        h_new = h * dec[..., None, None] + st
        return h_new, h
    h_init = (jnp.zeros((Bt, H, P, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))
    hT, h_prev = jax.lax.scan(
        scan_fn, h_init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)      # state entering each chunk

    # inter-chunk contribution
    in_decay = jnp.exp(cum)                       # decay from chunk start to t
    y_inter = jnp.einsum("bcln,bclh,bchpn->bclhp", Cf, in_decay, h_prev)

    y = (y_intra + y_inter).reshape(Bt, S, H, P)
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(out_dtype), hT


def ssd_decode_ref(x, dt, A, B, C, D, h, *, out_dtype=None):
    """Single-step SSD state update (AR decode).
    x: [Bt,H,P], dt: [Bt,H], B,C: [Bt,N], h: [Bt,H,P,N]."""
    out_dtype = out_dtype or x.dtype
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None])
    h = h * decay[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", xf * dtf[..., None], B.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", h, C.astype(jnp.float32))
    y = y + D.astype(jnp.float32)[None, :, None] * xf
    return y.astype(out_dtype), h


# --------------------------------------------------------------------------
# fused prologue/epilogue oracles
# --------------------------------------------------------------------------
#
# These compose the standalone oracles in EXACTLY the order (and with
# exactly the casts) the unfused op chain uses, so on the reference path a
# fused pipeline is bit-identical to the discrete chain it replaces —
# greedy decode stays token-identical when `fuse_epilogues` toggles.  The
# Pallas kernels compute the same math with streamed statistics and are
# tolerance-validated against these.

def norm_prologue_ref(x, *, norm, gamma, nbeta=None, eps):
    """Normalize the GEMM `a` operand (output in x.dtype, like ops.norm)."""
    if norm == "rmsnorm":
        return rmsnorm_ref(x, gamma, eps=eps)
    if norm == "layernorm":
        return layernorm_ref(x, gamma, nbeta, eps=eps)
    assert norm == "none", norm
    return x


def fused_matmul_ref(x, w, *, norm="none", gamma=None, nbeta=None,
                     w_scale=None, bias=None, residual=None,
                     activation="none", eps=RMS_EPS, compute_dtype=None,
                     dot_dtype=None, out_dtype=None):
    """act(norm(x) @ w + bias) cast to out_dtype, + residual.

    `compute_dtype`: operand cast before the dot (the policy compute
    dtype); `dot_dtype`: preferred_element_type of the dot (what `pdot`
    would emit); `out_dtype`: dtype of the result before the residual add.
    `w_scale` ([N] fp32): per-output-channel dequant scale for int8 `w` —
    applied to the dot output in fp32 before the (unquantized) bias, the
    same point the Pallas kernel folds it into the accumulator.
    """
    h = norm_prologue_ref(x, norm=norm, gamma=gamma, nbeta=nbeta, eps=eps)
    cd = compute_dtype or h.dtype
    od = dot_dtype or out_dtype or h.dtype
    y = jax.lax.dot_general(
        h.astype(cd), w.astype(cd),
        (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=od)
    if w_scale is not None:
        y = (y.astype(jnp.float32)
             * w_scale.astype(jnp.float32)).astype(y.dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if activation != "none":
        from repro.core.activations import get_activation
        y = get_activation(activation)(y)
    if out_dtype is not None:
        y = y.astype(out_dtype)
    if residual is not None:
        y = residual + y
    return y


def fused_matmul_swiglu_ref(x, w_gate, w_up, *, norm="none", gamma=None,
                            nbeta=None, wg_scale=None, wu_scale=None,
                            residual=None, eps=RMS_EPS,
                            compute_dtype=None, out_dtype=None):
    """silu(norm(x) @ wg) * (norm(x) @ wu) [+ residual] — the exact op
    chain of ops.matmul_swiglu's reference path with the pre-norm folded
    in front and the residual add behind.  `wg_scale`/`wu_scale`: int8
    per-output-channel dequant, applied in fp32 before the silu gate."""
    h = norm_prologue_ref(x, norm=norm, gamma=gamma, nbeta=nbeta, eps=eps)
    cd = compute_dtype or h.dtype
    od = out_dtype or h.dtype
    a = h.astype(cd)
    g = matmul_ref(a, w_gate.astype(cd), activation="none", out_dtype=od)
    u = matmul_ref(a, w_up.astype(cd), activation="none", out_dtype=od)
    gf = g.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    if wg_scale is not None:
        gf = gf * wg_scale.astype(jnp.float32)
        uf = uf * wu_scale.astype(jnp.float32)
    y = (jax.nn.silu(gf) * uf).astype(od)
    if residual is not None:
        y = residual + y
    return y


def residual_norm_ref(x, y, *, norm, gamma, nbeta=None, eps=RMS_EPS):
    """r = x + y; h = norm(r) — same two ops as the unfused chain.
    -> (h, r)."""
    r = x + y
    return norm_prologue_ref(r, norm=norm, gamma=gamma, nbeta=nbeta,
                             eps=eps), r
