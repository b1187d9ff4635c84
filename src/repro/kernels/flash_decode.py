"""Split-KV decode attention kernel — the paper's *distributed Softmax
primitive* (T4) at chip scope.

AR decode computes one query token against a long KV cache: a pure
memory-bound matrix-vector pass (the paper's <10%-FPU-utilization regime).
The cache is split into chunks; every chunk produces partial online-softmax
statistics (m, l, o) which are merged in a second stage — the same
max/rescale/sum tree the paper distributes across clusters.  The identical
merge rule combines *cross-chip* partials in core/distributed_softmax.py,
so chip-local and pod-level softmax use one primitive.

Grid: (B, n_chunks) with the chunk dim innermost; each step folds one
chunk of every KV head and partials are merged in-kernel through VMEM
scratch (single pass over the cache).  A chunk is a [chunk, KV*D] tile:
every block's last two dims stay TPU-tileable (a per-head (1, chunk, 1, D)
block is refused by the compiler).  The dense caches are stored
[B, S, KV, D], so `decode_attention` views them as [B, S, KV*D]; on the
TPU that reshape changes the minor dims' tiling and costs a relayout copy
of the cache (the dense path is not a serving hot path).

`paged_decode_attention` is the block-paged variant of the same primitive:
the cache is a global pool of fixed-size KV blocks and each slot owns an
ordered *block table* of pool indices.  The table (and the per-slot valid
lengths) ride in as scalar-prefetch operands: the grid's innermost
dimension walks table entries, the BlockSpec index map dereferences the
table to DMA the named block, and absent entries (unallocated / non-owned
shard) skip their fold — the grid still spans max_blocks cells per slot,
but the dot work (and, via consecutive-index pipelining, the block
fetches) tracks the blocks a slot actually owns, while the pool *capacity*
is decoupled from B x max_seq entirely.

The paged kernels read the pool in the layout it is stored in: every
layer's pools stacked, [L, NB, BS, KV*D] (launch/steps.cache_layout), one
token row holding all heads side by side.  The layer index rides in as one
more scalar-prefetch operand and the index map returns (layer, table[b, e],
0, 0), so a decode layer scan passes the whole stacked pool to every layer's
call and no per-layer slice, relayout or write-back of the pool is ever
made (viewing a [.., KV, D] pool as [.., KV*D] is NOT free on the TPU: it
changes the minor dims' tiling and copies the pool)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_merge(ci, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                  mask, live, sm_scale: float, k_scales=None, v_scales=None):
    """Shared split-KV cell body: fold one masked KV chunk's scores into the
    (m, l, acc) scratch with the online-softmax rescale rule, for every KV
    head of the chunk, initializing the scratch on the first chunk.
    `mask`: [G, chunk] validity of this chunk's positions (shared by all
    heads); `live`: scalar — False when the whole chunk is masked, skipping
    its dot work entirely (a fully-masked chunk is a no-op: corr = 1,
    p = 0).  The caller writes the output on the last chunk.

    q_ref: [1, KV, G, D]; k/v_ref: [1, chunk, KV*D] — one KV chunk with all
    heads side by side on the lane axis, so head h is the lane-aligned
    column slice [h*D, (h+1)*D).  Scratch: m/l [KV, G, 1], acc [KV, G, D].

    `k_scales`/`v_scales`: per-head fp32 dequant scalars (length KV) for
    int8 K/V chunks.  A scale is constant over its chunk's tokens and dims,
    so it commutes past both dots: scores pick up the K scale after Q.K^T
    and the P.V contribution picks up the V scale — exact dequantization
    without ever materializing fp K/V tiles."""
    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _fold():
        kv, d = q_ref.shape[1], q_ref.shape[3]
        for h in range(kv):
            q = q_ref[0, h]                             # [G, D]
            k = k_ref[0, :, h * d:(h + 1) * d]          # [chunk, D]
            v = v_ref[0, :, h * d:(h + 1) * d]
            if k_scales is not None:
                # int8 chunk: run the dots in fp32 (int8 values are exact)
                q = q.astype(jnp.float32)
                k = k.astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale
            if k_scales is not None:
                s = s * k_scales[h]
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                           # [G, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            m_ref[h] = m_new
            if v_scales is not None:
                pv = jax.lax.dot_general(
                    p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * v_scales[h]
            else:
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + pv


def _scratch(KV: int, G: int, D: int):
    return [pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32)]


def _normalized(acc_ref, l_ref):
    return acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, block_kv: int, window: int, sm_scale: float):
    """q_ref: [1, KV, G, D]; k/v_ref: [1, block_kv, KV*D];
    len_ref: scalar-prefetch [B] valid lengths; o_ref: [1, KV, G, D]."""
    b = pl.program_id(0)
    ci = pl.program_id(1)
    g = q_ref.shape[2]
    length = len_ref[b]
    pos = jax.lax.broadcasted_iota(jnp.int32, (g, block_kv), 1) + ci * block_kv
    mask = pos < length
    live = ci * block_kv < length
    if window > 0:
        mask &= pos >= length - window
        live &= (ci + 1) * block_kv > length - window
    _online_merge(ci, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                  mask=mask, live=live, sm_scale=sm_scale)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = _normalized(acc_ref, l_ref).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_kv", "interpret"))
def decode_attention(q, k_cache, v_cache, length, *, window=0, block_kv=512,
                     interpret=False):
    """q: [B, H, D]; caches: [B, S, KV, D]; length: [B] or scalar valid
    lengths.  Returns [B, H, D].  Grid (B, chunks): each step folds one
    chunk of every KV head (the cache rows are viewed as [B, S, KV*D], so
    a chunk is one contiguous DMA; on the TPU the view is a relayout copy,
    see the module docstring)."""
    B, H, D = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    block_kv = min(block_kv, S)
    sm_scale = float(1.0 / (D ** 0.5))
    pad = -S % block_kv
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = k_cache.shape[1]
    nc = Sp // block_kv
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    qr = q.reshape(B, KV, G, D)
    kr = k_cache.reshape(B, Sp, KV * D)
    vr = v_cache.reshape(B, Sp, KV * D)

    kv_spec = pl.BlockSpec((1, block_kv, KV * D),
                           lambda b, c, len_ref: (b, c, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, c, len_ref: (b, 0, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, KV, G, D),
                               lambda b, c, len_ref: (b, 0, 0, 0)),
        scratch_shapes=_scratch(KV, G, D),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_kv=block_kv, window=window,
                          sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(length, qr, kr, vr)
    return out.reshape(B, H, D)


# --------------------------------------------------------------------------
# paged split-KV decode
# --------------------------------------------------------------------------

def _paged_mask(tab_ref, len_ref, b, e, g: int, block_size: int):
    """-> (mask [G, BS], live scalar) for pool-block entry `e` of slot `b`:
    token t of entry e holds absolute position e*BS + t, masked when past
    the slot's length or when the table entry is absent (< 0: unallocated /
    non-owned shard).  `live` is False when the whole entry is masked —
    absent entries skip their fold (and their DMA collapses onto block 0,
    which consecutive-index pipelining fetches once), so per-step work
    tracks the blocks a slot actually owns."""
    pos = (jax.lax.broadcasted_iota(jnp.int32, (g, block_size), 1)
           + e * block_size)
    live = (tab_ref[b, e] >= 0) & (e * block_size < len_ref[b])
    return (pos < len_ref[b]) & (tab_ref[b, e] >= 0), live


def _paged_scales(tab_ref, ks_ref, vs_ref, b, e, kv: int):
    """Per-head dequant scalars for this grid step's pool block — one SMEM
    lookup per (block, head) in the flattened [NB*KV] scale tables.
    Absent entries (t < 0) read block 0's scales; their fold is dead
    (`live` is False)."""
    if ks_ref is None:
        return None, None
    t = jnp.maximum(tab_ref[b, e], 0) * kv
    return ([ks_ref[t + h] for h in range(kv)],
            [vs_ref[t + h] for h in range(kv)])


def _paged_fold(refs, quantized: bool, n_out: int, block_size: int,
                sm_scale: float):
    """Unpack the paged kernels' refs and fold this step's pool block.
    -> (e, outs, m_ref, l_ref, acc_ref)."""
    if quantized:
        _, tab_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref = refs[:8]
        rest = refs[8:]
    else:
        _, tab_ref, len_ref, q_ref, k_ref, v_ref = refs[:6]
        ks_ref = vs_ref = None
        rest = refs[6:]
    outs, (m_ref, l_ref, acc_ref) = rest[:n_out], rest[n_out:]
    b = pl.program_id(0)
    e = pl.program_id(1)
    mask, live = _paged_mask(tab_ref, len_ref, b, e, q_ref.shape[2],
                             block_size)
    ks, vs = _paged_scales(tab_ref, ks_ref, vs_ref, b, e, q_ref.shape[1])
    _online_merge(e, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                  mask=mask, live=live, sm_scale=sm_scale,
                  k_scales=ks, v_scales=vs)
    return e, outs, m_ref, l_ref, acc_ref


def _paged_decode_kernel(*refs, block_size: int, sm_scale: float,
                         quantized: bool):
    """q_ref: [1, KV, G, D]; k/v_ref: [1, block_size, KV*D] — the pool
    block the slot's table names for entry `e` in the layer the layer
    operand names (the index map dereferenced both), all heads; scalar
    prefetch: the layer index [1], tab_ref [B, MB] block tables (< 0 =
    absent), len_ref [B] valid lengths.  Quantized pools add ks/vs_ref:
    scalar-prefetch [NB*KV] fp32 per-block-per-head scales of that layer,
    flattened."""
    e, (o_ref,), m_ref, l_ref, acc_ref = _paged_fold(
        refs, quantized, 1, block_size, sm_scale)

    @pl.when(e == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = _normalized(acc_ref, l_ref).astype(o_ref.dtype)


def _paged_partials_kernel(*refs, block_size: int, sm_scale: float,
                           quantized: bool):
    """As _paged_decode_kernel but emits the raw (o, m, l) online-softmax
    partials instead of normalizing — the cross-shard T4 merge
    (core/attention.merge_partials) combines per-device pool shards."""
    e, (o_ref, mo_ref, lo_ref), m_ref, l_ref, acc_ref = _paged_fold(
        refs, quantized, 3, block_size, sm_scale)

    @pl.when(e == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = acc_ref[...]
        mo_ref[0] = m_ref[...]
        lo_ref[0] = l_ref[...]


def _paged_call(kernel, q, k_pool, v_pool, layer, block_tables, lengths,
                out_shape, out_specs, interpret, k_scale=None, v_scale=None):
    """Shared pallas_call plumbing for the paged kernels: grid (slot, table
    entry) with the layer index and the tables scalar-prefetched and
    dereferenced by the k/v index maps — each step DMAs exactly one owned
    pool block of one layer, every KV head at once, straight out of the
    stacked pool [L, NB, BS, KV*D] as it is stored.  Int8 pools
    additionally prefetch that layer's dequant scales, a [NB, KV] slice of
    the [L, NB, KV] stack flattened to [NB*KV]: scalar memory is 1 MiB on
    a v5e and pads a 2-D table's rows to 128 words, so neither the whole
    stack nor a [NB, KV] table fits at serving pool sizes (1600 blocks)."""
    B, KV, G, D = q.shape
    _, NB, BS, KVD = k_pool.shape
    assert KVD == KV * D, (k_pool.shape, q.shape)
    MB = block_tables.shape[1]
    sm_scale = float(1.0 / (D ** 0.5))
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    block_tables = block_tables.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    quantized = k_scale is not None

    def kv_index(b, e, lay_ref, tab_ref, *_pref):
        t = tab_ref[b, e]
        # absent -> any block, masked
        return (lay_ref[0], jnp.where(t < 0, 0, t), 0, 0)

    kv_spec = pl.BlockSpec((None, 1, BS, KVD), kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, e, *_pref: (b, 0, 0, 0)),
            kv_spec, kv_spec,
        ],
        out_specs=out_specs,
        scratch_shapes=_scratch(KV, G, D),
    )
    prefetch = (layer, block_tables, lengths)
    if quantized:
        prefetch += tuple(x[layer[0]].reshape(-1).astype(jnp.float32)
                          for x in (k_scale, v_scale))
    return pl.pallas_call(
        functools.partial(kernel, block_size=BS, sm_scale=sm_scale,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, q, k_pool, v_pool)


def _slot_spec(*block):
    return pl.BlockSpec((1, *block), lambda b, e, *_pref: (b, 0, 0, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lengths,
                           *, k_scale=None, v_scale=None, interpret=False):
    """Paged split-KV decode.  q: [B, H, D]; k/v_pool: [L, NB, BS, KV*D] —
    every layer's global pool of fixed-size KV blocks, stacked; layer:
    int32 scalar, the layer to attend; block_tables: [B, MB] int32 pool
    indices in sequence order (< 0 = absent entry); lengths: [B] valid
    tokens per slot.  Returns [B, H, D], softmax fully normalized
    (single-pool case; sharded pools use `paged_decode_partials`).

    `k_scale`/`v_scale` ([L, NB, KV] fp32): per-block-per-head dequant
    scales for int8 pools (quantize-on-write lives in the cache scatters)."""
    B, H, D = q.shape
    KV = k_pool.shape[-1] // D
    G = H // KV
    out = _paged_call(
        _paged_decode_kernel, q.reshape(B, KV, G, D), k_pool, v_pool, layer,
        block_tables, lengths,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        out_specs=_slot_spec(KV, G, D),
        interpret=interpret, k_scale=k_scale, v_scale=v_scale)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_partials(q, k_pool, v_pool, layer, block_tables, lengths, *,
                          k_scale=None, v_scale=None, interpret=False):
    """Paged split-KV decode emitting unnormalized online-softmax partials:
    -> (o [B, H, D] fp32 unnormalized, m [B, H], l [B, H]).  Operands as
    `paged_decode_attention` (stacked pools, a layer index).  Each cache
    shard runs this over its *local* pool (non-owned table entries < 0) and
    the T4 merge rule combines the shards — the pool is never gathered."""
    B, H, D = q.shape
    KV = k_pool.shape[-1] // D
    G = H // KV
    stat = jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32)
    o, m, l = _paged_call(
        _paged_partials_kernel, q.reshape(B, KV, G, D), k_pool, v_pool,
        layer, block_tables, lengths,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32),
                   stat, stat],
        out_specs=[_slot_spec(KV, G, D), _slot_spec(KV, G, 1),
                   _slot_spec(KV, G, 1)],
        interpret=interpret, k_scale=k_scale, v_scale=v_scale)
    return o.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)
