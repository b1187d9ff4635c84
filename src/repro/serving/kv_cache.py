"""KV-cache utilities for the serving engine.

The cache *layout* (paged pools vs dense rows, ring vs linear, sequence
sharding) is owned by launch/steps.cache_layout; this module materializes
zero-initialized caches and provides the host-side paging machinery:

  BlockAllocator       free-list over a global pool of fixed-size KV blocks;
                       the engine owns one per decode batch and keeps a
                       per-slot block table of the blocks each request holds.
  make_prefill_scatter jitted admission scatter: a freshly prefilled group's
                       compact KV goes straight into its assigned pool
                       blocks (per-block scatter), while dense leaves (SSM
                       state, ring caches, cross-attn memory) row-scatter
                       into the group's slots — no B x max_seq
                       dynamic_update_slice ever runs.
  insert_row           legacy single-row scatter (dense layouts).
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core.attention import SCALE_EPS

# One compiled zeros-builder per (shape, dtype, sharding) leaf, shared by
# every engine construction in the process — re-jitting a fresh lambda per
# leaf per call would recompile each time (the keys here are hashable, the
# struct pytrees are not, so the cache lives at leaf granularity).
_ZEROS_CACHE: dict = {}


def _zeros(shape, dtype, sharding=None):
    key = (tuple(shape), jnp.dtype(dtype).str, sharding)
    fn = _ZEROS_CACHE.get(key)
    if fn is None:
        build = functools.partial(jnp.zeros, tuple(shape), dtype)
        fn = (jax.jit(build) if sharding is None
              else jax.jit(build, out_shardings=sharding))
        _ZEROS_CACHE[key] = fn
    return fn()


def zero_caches(cache_struct, shardings=None):
    """Materialize zeroed caches matching the struct tree (optionally with
    shardings — the decode step's cache specs)."""
    if shardings is None:
        return jax.tree.map(lambda st: _zeros(st.shape, st.dtype),
                            cache_struct)
    return jax.tree.map(lambda st, sh: _zeros(st.shape, st.dtype, sh),
                        cache_struct, shardings)


@jax.jit
def insert_row(batch_caches, single_caches, row: int):
    """Scatter a single-request cache (batch dim 1) into row `row` of the
    batch caches.  Cache leaves are [count, B, ...]: dim 1 is the batch."""
    def ins(b, s):
        return jax.lax.dynamic_update_slice_in_dim(b, s.astype(b.dtype),
                                                   row, axis=1)
    return jax.tree.map(ins, batch_caches, single_caches)


# --------------------------------------------------------------------------
# block-paged pool
# --------------------------------------------------------------------------

class BlockAllocator:
    """Host-side refcounted free-list over a global pool of `num_blocks` KV
    blocks of `block_size` tokens each.  The engine allocates
    ceil(tokens / bs) blocks at admission, one more whenever a slot's decode
    position crosses a block boundary, and releases a request's blocks the
    moment it retires (or is preempted back to the queue) — pool occupancy
    tracks *live tokens*, not slots x max_seq.

    Refcounts (serving/prefix_cache.py): a block may be shared by several
    holders — decode slots reusing a cached prompt prefix, plus the prefix
    cache's radix index itself.  `alloc` hands out blocks at refcount 1,
    `retain` adds a holder, and `free` drops one — the block returns to the
    free list only when the last holder lets go.  When the free list cannot
    satisfy an `alloc`, the optional `reclaim` hook (the prefix cache's LRU
    evictor) is asked to release index-only blocks first, so cached prefixes
    survive exactly as long as the pool has room for them (lazy eviction
    replaces the pre-cache eager free).

    Invariant guards raise `RuntimeError` (not `assert`) so double frees and
    stale retains stay fatal under `python -O`; the free-set mirror makes the
    membership check O(1)."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(f"pool needs >= 1 block of >= 1 token: "
                             f"({num_blocks}, {block_size})")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # LIFO free list: freshly freed blocks are reused first (their pool
        # slots are the warmest in cache); the set mirrors it for O(1)
        # membership checks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._free_set = set(self._free)
        self._ref: List[int] = [0] * num_blocks
        self.peak_used = 0
        # lazy-reclaim hook: called with the shortfall when alloc() would
        # otherwise fail; returns how many blocks it pushed back to the
        # free list (serving/prefix_cache.py registers its LRU evictor)
        self.reclaim = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def refcount(self, block: int) -> int:
        """Live holder count for `block` (0 = on the free list)."""
        return self._ref[block]

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold `tokens` cache positions."""
        return -(-tokens // self.block_size)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop `n` blocks at refcount 1, or None (allocation is
        all-or-nothing) when the pool cannot satisfy the request even after
        asking `reclaim` to evict cached blocks."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free) and self.reclaim is not None:
            self.reclaim(n - len(self._free))
        if n > len(self._free):
            return None
        out = []
        for _ in range(n):
            b = self._free.pop()
            self._free_set.discard(b)
            self._ref[b] = 1
            out.append(b)
        self.peak_used = max(self.peak_used, self.num_used)
        return out

    def retain(self, blocks: List[int]) -> None:
        """Add one holder to each allocated block (prefix-cache sharing)."""
        for b in blocks:
            if self._ref[b] <= 0:
                raise RuntimeError(f"retain of unallocated block {b}")
            self._ref[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one holder from each block; a block whose last holder lets
        go returns to the free list."""
        if len(set(blocks)) != len(blocks):
            raise RuntimeError(f"double free within batch: {blocks}")
        for b in blocks:
            if b in self._free_set or self._ref[b] <= 0:
                raise RuntimeError(f"double free of block {b}")
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
                self._free_set.add(b)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("paged_segments", "block_size"))
def _prefill_scatter(caches, group_caches, slots, tables, *,
                     paged_segments, block_size: int):
    out = []
    for seg, new, paged in zip(caches, group_caches, paged_segments):
        d = {}
        quantized = paged and "ks" in seg
        for key, leaf in seg.items():
            if key in ("ks", "vs"):
                continue                 # written alongside their pools
            val = new[key]
            if paged and key in ("k", "v"):
                nb_pool = leaf.shape[1]
                n, S = val.shape[1], val.shape[2]
                ne = -(-S // block_size)            # entries this group fills
                pad = ne * block_size - S
                if pad:
                    val = jnp.pad(
                        val, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                # [count, n, ne, BS, KV, hd]: heads split for the scales,
                # flattened into the pool's [.., BS, KV*hd] rows on write
                val = val.reshape(val.shape[0], n, ne, block_size,
                                  *val.shape[3:])
                ids = tables[:, :ne]
                # -1 wraps in .at[]; route out of range so "drop" applies
                ids = jnp.where(ids >= 0, ids, nb_pool)
                if quantized:
                    # admission covers every written block from offset 0, so
                    # each block's scale is simply the per-head amax of the
                    # tokens landing in it (pad positions are zero — inert).
                    # The prefill step itself ran bf16: quantization happens
                    # once, here, on admission into the pool.
                    xf = val.astype(jnp.float32)
                    amax = jnp.max(jnp.abs(xf), axis=(3, 5))
                    s = jnp.maximum(amax, SCALE_EPS) / 127.0
                    q = jnp.clip(jnp.round(xf / s[:, :, :, None, :, None]),
                                 -127, 127).astype(jnp.int8)
                    d[key] = leaf.at[:, ids].set(q.reshape(*q.shape[:4], -1),
                                                 mode="drop")
                    sk = key + "s"
                    d[sk] = seg[sk].at[:, ids].set(s, mode="drop")
                else:
                    d[key] = leaf.at[:, ids].set(
                        val.reshape(*val.shape[:4], -1).astype(leaf.dtype),
                        mode="drop")
            else:
                d[key] = leaf.at[:, slots].set(val.astype(leaf.dtype))
        out.append(d)
    return tuple(out)


def make_prefill_scatter(paged_segments, block_size: int):
    """The jitted admission scatter for one engine layout.

    scatter(caches, group_caches, slots, tables) -> caches

      caches        the live decode cache pytree (paged segments: k/v pools
                    [count, NB, BS, KV*hd]; donated — updated in place)
      group_caches  a prefilled admission group's compact caches (paged
                    leaves [count, nB, S, KV, hd] at prompt length; dense
                    leaves [count, nB, ...])
      slots         [nB] int32 decode-slot index per group row
      tables        [nB, MB] int32 assigned pool blocks in sequence order
                    (-1 beyond the allocation)

    Paged k/v leaves scatter per assigned block; every other leaf scatters
    per slot row.  The jit lives at module level with the layout as static
    args, so compiles (one per (nB, prompt-length) shape) are shared across
    engine constructions."""
    return functools.partial(_prefill_scatter,
                             paged_segments=tuple(bool(p)
                                                  for p in paged_segments),
                             block_size=block_size)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("paged_segments",))
def _block_copy(caches, src, dst, *, paged_segments):
    out = []
    for seg, paged in zip(caches, paged_segments):
        d = dict(seg)
        if paged:
            for key in ("k", "v", "ks", "vs"):
                if key not in d:
                    continue
                leaf = d[key]       # pools [count, NB, BS, KV*hd];
                #                     scales [count, NB, KV] — dim 1 is NB
                #                     for both, so one copy path serves them
                row = jax.lax.dynamic_index_in_dim(leaf, src, axis=1,
                                                   keepdims=True)
                d[key] = jax.lax.dynamic_update_slice_in_dim(leaf, row, dst,
                                                             axis=1)
        out.append(d)
    return tuple(out)


def make_block_copy(paged_segments):
    """The jitted copy-on-write block duplicator for one engine layout.

    copy(caches, src, dst) -> caches

    Copies pool block `src` into pool block `dst` across every paged k/v
    leaf (dense leaves pass through untouched).  The prefix cache calls this
    before a slot writes into a *shared* block — a partially filled tail
    whose content other holders (the radix index, or another slot) still
    depend on — so the writer mutates its private duplicate instead.
    `src`/`dst` are traced scalars: one compile serves every block pair."""
    segs = tuple(bool(p) for p in paged_segments)

    def copy(caches, src, dst):
        return _block_copy(caches, jnp.asarray(src, jnp.int32),
                           jnp.asarray(dst, jnp.int32), paged_segments=segs)

    return copy


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("paged_segments",))
def _row_copy(caches, src_blk, src_off, dst_blk, dst_off, *, paged_segments):
    out = []
    for seg, paged in zip(caches, paged_segments):
        d = dict(seg)
        if paged:
            for key in ("k", "v"):      # NOT ks/vs: per-block int8 scales
                #                         cannot move one row at a time
                if key not in d:
                    continue
                leaf = d[key]           # [count, NB, BS, KV*hd]
                count, _, _, width = leaf.shape
                row = jax.lax.dynamic_slice(
                    leaf, (0, src_blk, src_off, 0), (count, 1, 1, width))
                d[key] = jax.lax.dynamic_update_slice(
                    leaf, row, (0, dst_blk, dst_off, 0))
        out.append(d)
    return tuple(out)


def make_row_copy(paged_segments):
    """The jitted single-position KV mover for one engine layout.

    copy(caches, src_blk, src_off, dst_blk, dst_off) -> caches

    Copies ONE cache position — (block, in-block offset) — across every
    paged k/v pool leaf.  Tree-speculative commit uses this to compact the
    accepted root path's KV into the slot's canonical positions: tree
    nodes scatter their KV at pos0 + node_index (unique per node), but the
    committed sequence needs depth-d's KV at pos0 + d.  Rope was applied
    at the node's LOGICAL position (pos0 + depth) during verify, so the
    move is a pure byte relocation — no re-rotation.  Sources sit strictly
    above their destinations in flatten order, and copies run in
    increasing depth, so moves never clobber a pending source.  int8 pools
    are excluded at the runner level (per-block scales pin entries to
    their block), not here.  All four indices are traced scalars: one
    compile serves every move."""
    segs = tuple(bool(p) for p in paged_segments)

    def copy(caches, src_blk, src_off, dst_blk, dst_off):
        return _row_copy(caches, jnp.asarray(src_blk, jnp.int32),
                         jnp.asarray(src_off, jnp.int32),
                         jnp.asarray(dst_blk, jnp.int32),
                         jnp.asarray(dst_off, jnp.int32),
                         paged_segments=segs)

    return copy
