"""Axis-name-safe collective wrappers for manual-SPMD model code.

All model layers are written against these: when the axis tuple is empty
(no mesh / axis not present) every collective degrades to identity, so the
same layer code runs unsharded in unit tests and sharded under shard_map.

`psum_scatter` optionally routes through the literal binary-tree schedule
(core.tree_reduce) — the paper's log2-depth cluster-to-cluster reduction —
selected by `set_reduce_method("tree")` for the §Perf comparison.
"""
from __future__ import annotations

import threading
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.tree_reduce import tree_psum_scatter

Axes = Union[str, Tuple[str, ...], None]

_STATE = threading.local()


def shard_map(fn, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off (manual-SPMD model
    code psums replication axes explicitly)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def set_reduce_method(method: str) -> None:
    assert method in ("ring", "tree"), method
    _STATE.reduce_method = method


def get_reduce_method() -> str:
    return getattr(_STATE, "reduce_method", "ring")


def _norm(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def one_axis_size(a) -> int:
    """Static size of one named axis."""
    return jax.lax.axis_size(a)


def axis_size(axes: Axes) -> int:
    n = 1
    for a in _norm(axes):
        n *= one_axis_size(a)
    return n


def axis_index(axes: Axes):
    """Linearized index over possibly-multiple axes (C order: first major).
    A static 0 when the axes span one device (no axes, or all of size 1),
    so offsets built from it stay Python ints and static-offset kernels
    (ops.flash_attention) keep their Pallas path."""
    axes = _norm(axes)
    if axis_size(axes) == 1:
        return 0
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * one_axis_size(a) + jax.lax.axis_index(a)
    return idx


def psum(x, axes: Axes):
    axes = _norm(axes)
    return jax.lax.psum(x, axes) if axes else x


def pmax(x, axes: Axes):
    axes = _norm(axes)
    return jax.lax.pmax(x, axes) if axes else x


def all_gather(x, axes: Axes, *, axis: int = 0, tiled: bool = True):
    """Gather over possibly-multiple mesh axes along array dim `axis`.
    Multi-axis order matches `axis_index` (first listed = major)."""
    for a in reversed(_norm(axes)):
        x = jax.lax.all_gather(x, a, axis=axis, tiled=tiled)
    return x


def all_gather_fp8(x, axes: Axes, *, axis: int = 0):
    """Activation all-gather with fp8(E4M3) wire payloads (§Perf P3c): cast
    before the gather, restore the dtype after.  Halves the dominant
    Megatron-SP gather bytes; softmax/norm math upstream stays fp32."""
    if not _norm(axes):
        return x
    dt = x.dtype
    return all_gather(x.astype(jnp.float8_e4m3fn), axes,
                      axis=axis).astype(dt)


def psum_scatter(x, axes: Axes, *, scatter_dimension: int = 0,
                 tiled: bool = True):
    axes = _norm(axes)
    if not axes:
        return x
    if get_reduce_method() == "tree" and len(axes) == 1:
        return tree_psum_scatter(x, axes[0], scatter_dim=scatter_dimension)
    for a in axes:  # scatter over major axis first => index math matches
        x = jax.lax.psum_scatter(x, a, scatter_dimension=scatter_dimension,
                                 tiled=tiled)
    return x


def pargmax(values, axes: Axes, *, index_offset):
    """Global argmax over a sharded last dim.  `values`: [..., Nl] local;
    `index_offset`: scalar global offset of this shard's column 0.
    Returns (max [..." ], argmax-global-index [...])."""
    loc_max = values.max(axis=-1)
    loc_arg = values.argmax(axis=-1).astype(jnp.int32) + index_offset
    g_max = pmax(loc_max, axes)
    # tie-break to the lowest index among winners
    cand = jnp.where(loc_max >= g_max, loc_arg, jnp.iinfo(jnp.int32).max)
    axes_n = _norm(axes)
    g_arg = jax.lax.pmin(cand, axes_n) if axes_n else cand
    return g_max, g_arg
