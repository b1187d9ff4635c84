"""The yardstick's arithmetic: chip peaks, and the operations and bytes
that the model's work needs, counted from the configuration's shapes.

Counts are of the work the tokens need, whatever implements it: padding
rows, padded buckets and recomputed tokens are never counted, so a
kernel that does more work than it must shows a lower share.  A
multiply-add is 2 operations; bf16 is 2 bytes.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s per chip
    hbm_bytes: float        # bytes/s per chip
    source: str


# Keyed by jax's device_kind.  A device the table does not list is an
# error: no share of a peak is computed against a guess.
PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         source="Google Cloud documentation, 'TPU v5e' "
                                "(per chip)"),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table lists {sorted(PEAKS)}")
    return PEAKS[device_kind]


def gemm_shapes(m):
    """(K, N) of each weight matrix of one layer, in the order the layer
    applies them: q, k, v, o, gate, up, down."""
    E, H, KV, hd, F = m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff
    return [(E, H * hd), (E, KV * hd), (E, KV * hd), (H * hd, E),
            (E, F), (E, F), (F, E)]


def layer_weight_params(m) -> int:
    return sum(k * n for k, n in gemm_shapes(m))


def gemm_flops(m, tokens: int) -> float:
    """All layers' weight GEMMs over `tokens` rows."""
    return 2.0 * tokens * layer_weight_params(m) * m.n_layers


def gemm_bytes(m, tokens: int) -> float:
    """Bytes the layers' GEMMs must move for one pass over `tokens` rows:
    every weight once, each GEMM's input and output rows once."""
    act = sum(k + n for k, n in gemm_shapes(m)) * tokens
    return float(BF16 * (layer_weight_params(m) + act) * m.n_layers)


def head_flops(m, rows: int) -> float:
    """The logits of `rows` positions over the vocabulary."""
    return 2.0 * rows * m.d_model * m.vocab


def head_bytes(m, rows: int) -> float:
    """The head's weights once, its input rows in bf16 and its float32
    logits out."""
    return float(BF16 * m.d_model * (m.vocab + rows) + 4 * rows * m.vocab)


def attn_flops(m, keys: Iterable[int]) -> float:
    """Scores and weighted values of every layer for query rows that
    attend `keys[i]` positions each (causal: a row at position p attends
    p + 1)."""
    return 4.0 * m.n_heads * m.head_dim * float(sum(keys)) * m.n_layers


def causal_keys(start: int, stop: int) -> int:
    """Sum over positions p in [start, stop) of p + 1 keys."""
    return (stop * (stop + 1) - start * (start + 1)) // 2


def prefill_flops(m, start: int, stop: int) -> float:
    """Model operations to encode positions [start, stop) of a sequence
    whose positions before `start` are cached, and sample its next token."""
    n = stop - start
    return (gemm_flops(m, n) + attn_flops(m, [causal_keys(start, stop)])
            + head_flops(m, 1))


def decode_flops(m, positions: Iterable[int]) -> float:
    """Model operations of decode tokens whose inputs sit at `positions`
    (each attends its position + 1 keys and samples one token)."""
    pos = list(positions)
    return (gemm_flops(m, len(pos)) + attn_flops(m, [p + 1 for p in pos])
            + head_flops(m, len(pos)))


def chunk_attn_work(m, start: int, stop: int):
    """(operations, bytes) that paged chunk attention needs for query
    positions [start, stop) over a cache of `stop` positions: the scores
    and values of every layer, with the cached K and V read once and the
    queries and outputs once."""
    flops = attn_flops(m, [causal_keys(start, stop)])
    kv = 2 * stop * m.n_kv_heads * m.head_dim
    qo = 2 * (stop - start) * m.n_heads * m.head_dim
    return flops, float(BF16 * (kv + qo) * m.n_layers)


def bound_s(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes)
