"""Kernel dispatch layer: Pallas TPU kernels <-> pure-jnp references.

Every model-facing op goes through this module.  Dispatch modes:

  ``auto``    (default) Pallas on TPU, reference elsewhere.  The reference
              implementations compute the *same math in the same precision*
              (fp32 accumulation / fp32 softmax), so CPU dry-run lowering
              produces representative FLOP/byte counts while TPU execution
              hits the hand-tiled kernels.  On TPU every auto dispatch is
              counted by op name (`dispatch_record()`), so a dispatch that
              took the reference there is visible (`hidden_fallbacks()`).
  ``pallas``            force compiled Pallas (TPU only).
  ``interpret``         force Pallas interpret mode (CPU correctness runs).
  ``ref``               force the jnp oracle.

Set with `repro.kernels.ops.set_mode(...)` or env `REPRO_KERNEL_MODE`
(validated at read time — a typo'd mode raises instead of silently falling
through dispatch).

Fused entry points (the prologue/epilogue pipeline)
---------------------------------------------------
The block stack emits *fused* GEMM pipelines by default (`fuse_epilogues`
on the sharding `Plan`): the pre-norm, bias/activation, and residual-add of
each transformer sub-layer fold into the GEMM that consumes/produces them,
so those [T, E] intermediates never round-trip HBM.  The declarative specs
live in `kernels/epilogue.py` (`Prologue`, `Epilogue`); the entry points:

  ``fused_matmul(x, w, prologue=, epilogue=)``     norm -> GEMM -> bias/
                                                   act/residual/cast
  ``fused_matmul_swiglu(x, wg, wu, prologue=, residual=)``
                                                   norm -> gated GEMM pair
                                                   -> silu-mul -> residual
  ``residual_norm(x, y, params, kind)``            r = x + y; h = norm(r)
                                                   in one pass (the spot a
                                                   GEMM can't absorb)
  ``expert_swiglu(xe, wg, wu)``                    batched per-expert gated
                                                   GEMMs (MoE), silu-mul
                                                   kept in VMEM

On the reference path these compose the standalone oracles in exactly the
unfused order/casts (bit-identical — greedy decode is token-identical when
fusion toggles); on the Pallas path they hit the streamed-statistics fused
kernels in `kernels/matmul.py` / `kernels/rmsnorm.py`.  Reference-path
fused pipelines run under ``vmemk_*`` named scopes so the HLO-based
roofline (analysis/hlo.py) attributes their eliminated intermediate
traffic correctly.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import matmul as _mm
from repro.kernels import rmsnorm as _norm
from repro.kernels import ssd as _ssd
from repro.kernels.epilogue import (LN_EPS, RMS_EPS, Epilogue, Prologue,
                                    norm_prologue)

__all__ = [
    "Epilogue", "Prologue", "norm_prologue", "get_mode", "set_mode",
    "kernel_mode", "flash_attention", "decode_attention",
    "paged_decode_attention", "paged_decode_partials",
    "paged_chunk_partials", "split_quantized", "matmul", "matmul_swiglu",
    "fused_matmul",
    "fused_matmul_swiglu", "expert_swiglu", "residual_norm", "rmsnorm",
    "layernorm", "norm", "ssd", "ssd_decode", "dispatch_record",
    "reset_dispatch_record", "hidden_fallbacks", "KERNEL_OPS", "REF_ONLY",
]

_STATE = threading.local()
_VALID = ("auto", "pallas", "interpret", "ref")


def _default_mode() -> str:
    mode = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode not in _VALID:
        raise ValueError(
            f"REPRO_KERNEL_MODE={mode!r} is not a valid kernel mode; "
            f"expected one of {_VALID}")
    return mode


def get_mode() -> str:
    return getattr(_STATE, "mode", None) or _default_mode()


def set_mode(mode: str) -> None:
    assert mode in _VALID, mode
    _STATE.mode = mode


@contextlib.contextmanager
def kernel_mode(mode: str):
    prev = getattr(_STATE, "mode", None)
    set_mode(mode)
    try:
        yield
    finally:
        _STATE.mode = prev


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    mode = get_mode()
    if mode == "ref":
        return False, False
    if mode == "pallas":
        return True, False
    if mode == "interpret":
        return True, True
    return (True, False) if _on_tpu() else (False, False)


# ops with a Pallas kernel, and those without one (the reference IS their
# TPU path)
KERNEL_OPS = ("flash_attention", "decode_attention", "paged_decode_attention",
              "paged_decode_partials.stacked", "matmul", "matmul_swiglu",
              "fused_matmul", "fused_matmul_swiglu", "expert_swiglu",
              "residual_norm", "rmsnorm", "layernorm", "ssd")
REF_ONLY = ("paged_chunk_partials", "ssd_decode")
_RECORD: dict = {}            # op -> {"pallas": n, "ref": n}
_RECORD_LOCK = threading.Lock()


def _note(op: str, pallas: bool) -> bool:
    """Count one trace-time dispatch of `op` in auto mode on a TPU backend
    (explicitly forced modes are never counted); returns `pallas`."""
    if get_mode() == "auto" and _on_tpu():
        with _RECORD_LOCK:
            row = _RECORD.setdefault(op, {"pallas": 0, "ref": 0})
            row["pallas" if pallas else "ref"] += 1
    return pallas


def dispatch_record() -> dict:
    """{op: {"pallas": n, "ref": n}} of the auto-mode dispatches traced on
    TPU since the last `reset_dispatch_record()`."""
    with _RECORD_LOCK:
        return {op: dict(row) for op, row in _RECORD.items()}


def reset_dispatch_record() -> None:
    with _RECORD_LOCK:
        _RECORD.clear()


def hidden_fallbacks() -> list:
    """Ops with a Pallas kernel that took the reference on TPU."""
    return sorted(op for op, row in dispatch_record().items()
                  if row["ref"] and op in KERNEL_OPS)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_kv=128):
    """q: [B, Sq, H, D]; k, v: [B, Skv, KV, D] -> [B, Sq, H, D].

    `q_offset` may be a traced scalar (sequence-parallel shards); the Pallas
    kernel requires it static, so traced offsets route to the online-softmax
    reference — which mirrors the FA-2 dataflow (KV-block scan, no S^2
    materialization), keeping dry-run FLOP/byte counts representative.
    """
    use, interp = _use_pallas()
    if _note("flash_attention", use and isinstance(q_offset, int)):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, block_q=block_q,
                                   block_kv=block_kv, interpret=interp)
    # "vmemk": score/probability intermediates live in VMEM in the Pallas
    # kernel — analysis/hlo.py zeroes their HBM-traffic contribution
    with jax.named_scope("vmemk_flash"):
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                        q_offset=q_offset,
                                        block_kv=max(block_kv, 512))


def decode_attention(q, k_cache, v_cache, length, *, window=0, block_kv=512):
    """q: [B, H, D]; caches: [B, S, KV, D]; length: [B] valid entries."""
    use, interp = _use_pallas()
    if _note("decode_attention", use):
        return _fd.decode_attention(q, k_cache, v_cache, length,
                                    window=window, block_kv=block_kv,
                                    interpret=interp)
    return _ref.decode_attention_ref(q, k_cache, v_cache, length,
                                     window=window)


def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lengths,
                           *, k_scale=None, v_scale=None):
    """Block-paged decode.  q: [B, H, D]; k/v_pool: [L, NB, BS, KV*D]
    stacked per-layer pools, as stored; layer: int32 scalar, the layer
    attended; block_tables: [B, MB] int32 pool indices (< 0 = absent
    entry); lengths: [B] valid tokens per slot.  Fully normalized output.
    `k_scale`/`v_scale` ([L, NB, KV] fp32): dequant scales for int8
    pools."""
    use, interp = _use_pallas()
    if _note("paged_decode_attention", use):
        return _fd.paged_decode_attention(q, k_pool, v_pool, layer,
                                          block_tables, lengths,
                                          k_scale=k_scale, v_scale=v_scale,
                                          interpret=interp)
    k, v, ks, vs = _layer_of(layer, k_pool, v_pool, k_scale, v_scale)
    return _ref.paged_decode_attention_ref(q, k, v, block_tables, lengths,
                                           k_scale=ks, v_scale=vs)


def paged_decode_partials(q, k_pool, v_pool, layer, block_tables, lengths, *,
                          k_scale=None, v_scale=None):
    """Block-paged decode partials -> (o unnormalized [B, H, D] fp32,
    m [B, H], l [B, H]) for the cross-shard online-softmax merge
    (core/attention.merge_partials); same operands as
    `paged_decode_attention`, run per cache shard on its local pools.  The
    kernel reads the stacked pools in place (no per-layer slice), and is
    counted as "paged_decode_partials.stacked"."""
    use, interp = _use_pallas()
    if _note("paged_decode_partials.stacked", use):
        return _fd.paged_decode_partials(q, k_pool, v_pool, layer,
                                         block_tables, lengths,
                                         k_scale=k_scale, v_scale=v_scale,
                                         interpret=interp)
    k, v, ks, vs = _layer_of(layer, k_pool, v_pool, k_scale, v_scale)
    return _ref.paged_decode_partials_ref(q, k, v, block_tables, lengths,
                                          k_scale=ks, v_scale=vs)


def _layer_of(layer, *stacked):
    """One layer of each stacked [L, ...] operand (None passes): the
    reference's view; the kernel indexes the stack in place."""
    return tuple(None if x is None else x[layer] for x in stacked)


def paged_chunk_partials(q, k_pool, v_pool, block_tables, q_pos, lengths, *,
                         k_scale=None, v_scale=None, tree_mask=None):
    """Chunked-prefill partials -> (o unnormalized [B, C, H, D] fp32,
    m [B, C, H], l [B, C, H]) over one layer's pools [NB, BS, KV*D]
    (scales [NB, KV]); q_pos [B, C] gives each query's absolute
    position for causal masking.  Run per cache shard on its local pool,
    merged with core/attention.merge_partials like the decode partials.
    `tree_mask` ([B, C, C] bool, optional) switches the intra-chunk mask
    from causal to an explicit ancestor matrix for tree-speculative verify
    (committed prefix + own ancestors only; see the reference oracle).

    No hand kernel yet: a prefill chunk is GEMM-throughput-bound on the
    same projections the dense prefill runs, and the score/probability
    intermediates are bounded by C x S — the reference keeps math and
    precision identical to the paged decode oracle (vmemk scope: the
    intermediates live in VMEM once a Pallas chunk kernel lands)."""
    _note("paged_chunk_partials", False)
    with jax.named_scope("vmemk_chunk"):
        return _ref.paged_chunk_partials_ref(q, k_pool, v_pool, block_tables,
                                             q_pos, lengths, k_scale=k_scale,
                                             v_scale=v_scale,
                                             tree_mask=tree_mask)


# --------------------------------------------------------------------------
# GEMM + fused prologues/epilogues (T1/T5)
# --------------------------------------------------------------------------

def split_quantized(w):
    """Unpack a weight-only-int8 param (models/quantize.quantize_params):
    {"q": int8 [K, N], "scale": fp32 [N]} -> (q, scale); a plain array
    passes through as (w, None).  Every GEMM entry point accepts either."""
    if isinstance(w, dict):
        return w["q"], w["scale"]
    return w, None


def matmul(a, b, *, activation="none", out_dtype=None,
           block_m=128, block_n=128, block_k=512):
    b, b_scale = split_quantized(b)
    use, interp = _use_pallas()
    if _note("matmul", use and a.ndim == 2):
        return _mm.matmul(a, b, activation=activation, b_scale=b_scale,
                          out_dtype=out_dtype, block_m=block_m,
                          block_n=block_n, block_k=block_k,
                          interpret=interp)
    if b_scale is not None:
        return _ref.fused_matmul_ref(a, b, w_scale=b_scale,
                                     activation=activation,
                                     compute_dtype=a.dtype,
                                     dot_dtype=out_dtype,
                                     out_dtype=out_dtype or a.dtype)
    return _ref.matmul_ref(a, b, activation=activation, out_dtype=out_dtype)


def matmul_swiglu(a, b_gate, b_up, *, out_dtype=None,
                  block_m=128, block_n=128, block_k=512):
    """o = silu(A @ Bg) * (A @ Bu), single fused pass."""
    b_gate, g_scale = split_quantized(b_gate)
    b_up, u_scale = split_quantized(b_up)
    use, interp = _use_pallas()
    if _note("matmul_swiglu", use and a.ndim == 2):
        return _mm.matmul_swiglu(a, b_gate, b_up, bg_scale=g_scale,
                                 bu_scale=u_scale, out_dtype=out_dtype,
                                 block_m=block_m, block_n=block_n,
                                 block_k=block_k, interpret=interp)
    out_dtype = out_dtype or a.dtype
    with jax.named_scope("vmemk_mlp"):
        if g_scale is not None:
            return _ref.fused_matmul_swiglu_ref(
                a, b_gate, b_up, wg_scale=g_scale, wu_scale=u_scale,
                compute_dtype=a.dtype, out_dtype=out_dtype)
        g = _ref.matmul_ref(a, b_gate, activation="none", out_dtype=out_dtype)
        u = _ref.matmul_ref(a, b_up, activation="none", out_dtype=out_dtype)
        return (jax.nn.silu(g.astype(jnp.float32))
                * u.astype(jnp.float32)).astype(out_dtype)


def _prologue_fields(prologue):
    if prologue is None:
        return dict(norm="none", gamma=None, nbeta=None, eps=RMS_EPS)
    return dict(norm=prologue.kind, gamma=prologue.scale, nbeta=prologue.bias,
                eps=prologue.eps)


def fused_matmul(x, w, *, prologue=None, epilogue=None, compute_dtype=None,
                 dot_dtype=None, block_m=128, block_n=128, block_k=512):
    """y = epilogue(norm(x) @ w);  x: [..., K], w: [K, N] -> [..., N].

    The model-facing fused GEMM: `prologue` (kernels.epilogue.Prologue)
    normalizes x in-register before the K-loop; `epilogue`
    (kernels.epilogue.Epilogue) applies bias + activation + residual-add +
    output cast in the accumulator before the single store.  With both None
    this is a plain GEMM emitting `dot_dtype`.

    `compute_dtype`: operand dtype of the contraction (the policy compute
    dtype); `dot_dtype`: preferred_element_type the unfused `pdot` would
    emit (the reference path matches it exactly for bit-identical fallback).
    Quantized weight dicts ({"q": int8, "scale": fp32 [N]}) stream the int8
    tiles and fold the dequant scale into the fp32 accumulator epilogue.
    """
    w, w_scale = split_quantized(w)
    ep = epilogue or Epilogue()
    out_dtype = ep.out_dtype or dot_dtype or x.dtype
    use, interp = _use_pallas()
    if _note("fused_matmul", use):
        lead = x.shape[:-1]
        K = x.shape[-1]
        N = w.shape[-1]
        x2 = x.reshape(-1, K)
        cd = compute_dtype or x.dtype
        if prologue is None:
            x2 = x2.astype(cd)      # normalized operands stay fp32 in-kernel
        # int8 weights stream uncast — the kernel casts tiles in-register
        wk = w if w_scale is not None else w.astype(cd)
        res2 = (ep.residual.reshape(-1, N) if ep.residual is not None
                else None)
        pf = _prologue_fields(prologue)
        out = _mm.matmul(x2, wk, activation=ep.activation, b_scale=w_scale,
                         bias=ep.bias, residual=res2, out_dtype=out_dtype,
                         block_m=block_m, block_n=block_n, block_k=block_k,
                         interpret=interp, **pf)
        return out.reshape(*lead, N)
    pf = _prologue_fields(prologue)
    with jax.named_scope("vmemk_fused_mm"):
        return _ref.fused_matmul_ref(
            x, w, w_scale=w_scale, bias=ep.bias, residual=ep.residual,
            activation=ep.activation, compute_dtype=compute_dtype,
            dot_dtype=dot_dtype, out_dtype=out_dtype, **pf)


def fused_matmul_swiglu(x, wg, wu, *, prologue=None, residual=None,
                        compute_dtype=None, out_dtype=None,
                        block_m=128, block_n=128, block_k=512):
    """y = silu(norm(x) @ wg) * (norm(x) @ wu) [+ residual]."""
    wg, g_scale = split_quantized(wg)
    wu, u_scale = split_quantized(wu)
    use, interp = _use_pallas()
    if _note("fused_matmul_swiglu", use):
        lead = x.shape[:-1]
        K = x.shape[-1]
        N = wg.shape[-1]
        x2 = x.reshape(-1, K)
        cd = compute_dtype or x.dtype
        if prologue is None:
            x2 = x2.astype(cd)
        wgk = wg if g_scale is not None else wg.astype(cd)
        wuk = wu if u_scale is not None else wu.astype(cd)
        res2 = residual.reshape(-1, N) if residual is not None else None
        pf = _prologue_fields(prologue)
        out = _mm.matmul_swiglu(x2, wgk, wuk, bg_scale=g_scale,
                                bu_scale=u_scale, residual=res2,
                                out_dtype=out_dtype, block_m=block_m,
                                block_n=block_n, block_k=block_k,
                                interpret=interp, **pf)
        return out.reshape(*lead, N)
    pf = _prologue_fields(prologue)
    with jax.named_scope("vmemk_fused_mlp"):
        return _ref.fused_matmul_swiglu_ref(
            x, wg, wu, wg_scale=g_scale, wu_scale=u_scale,
            residual=residual, compute_dtype=compute_dtype,
            out_dtype=out_dtype, **pf)


def expert_swiglu(xe, wg, wu, *, compute_dtype=None, out_dtype=None):
    """Batched per-expert gated FFN: xe [NE, C, E] @ wg/wu [NE, E, F] ->
    silu(g) * u [NE, C, F].  The silu-mul epilogue never leaves VMEM; the
    Pallas path vmaps the fused swiglu kernel over the expert dim."""
    out_dtype = out_dtype or xe.dtype
    use, interp = _use_pallas()
    if _note("expert_swiglu", use):
        cd = compute_dtype or xe.dtype
        import functools
        f = functools.partial(_mm.matmul_swiglu, out_dtype=out_dtype,
                              interpret=interp)
        return jax.vmap(f)(xe.astype(cd), wg.astype(cd), wu.astype(cd))
    cd = compute_dtype or xe.dtype
    with jax.named_scope("vmemk_moe"):
        g = jax.lax.dot_general(xe.astype(cd), wg.astype(cd),
                                (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=out_dtype)
        u = jax.lax.dot_general(xe.astype(cd), wu.astype(cd),
                                (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=out_dtype)
        return (jax.nn.silu(g.astype(jnp.float32))
                * u.astype(jnp.float32)).astype(out_dtype)


def residual_norm(x, y, params, kind: str):
    """Fused residual-add + pre-norm: r = x + y; h = norm(r) in one pass —
    the sub-layer boundary a GEMM epilogue can't absorb (the sum is both
    the next residual and the norm input).  -> (h, r)."""
    use, interp = _use_pallas()
    if _note("residual_norm", use):
        if kind == "rmsnorm":
            return _norm.residual_rmsnorm(x, y, params["scale"],
                                          interpret=interp)
        return _norm.residual_layernorm(x, y, params["scale"],
                                        params["bias"], interpret=interp)
    r = x + y
    with jax.named_scope("vmemk_fused_norm"):
        if kind == "rmsnorm":
            h = _ref.rmsnorm_ref(r, params["scale"])
        else:
            h = _ref.layernorm_ref(r, params["scale"], params["bias"])
    return h, r


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------

def rmsnorm(x, gamma, *, eps=RMS_EPS):
    use, interp = _use_pallas()
    if _note("rmsnorm", use):
        return _norm.rmsnorm(x, gamma, eps=eps, interpret=interp)
    return _ref.rmsnorm_ref(x, gamma, eps=eps)


def layernorm(x, gamma, beta, *, eps=LN_EPS):
    use, interp = _use_pallas()
    if _note("layernorm", use):
        return _norm.layernorm(x, gamma, beta, eps=eps, interpret=interp)
    return _ref.layernorm_ref(x, gamma, beta, eps=eps)


def norm(x, params, kind: str):
    """Dispatch on the config's norm kind; params: {"scale": ...[, "bias"]}"""
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params["bias"])


# --------------------------------------------------------------------------
# Mamba2 SSD
# --------------------------------------------------------------------------

def ssd(x, dt, A, B, C, D, *, chunk=128):
    """x: [Bt, S, H, P] -> (y, h_final).  Chunked state-space-duality scan.

    TPU dispatch prefers the v2 multi-head kernel (grid (B, chunks), all
    heads per cell: B/C stream once per chunk — §Perf P2 kernel design);
    falls back to the per-head grid when the [H,P,N] state would overflow
    VMEM."""
    use, interp = _use_pallas()
    if _note("ssd", use and x.shape[1] % min(chunk, x.shape[1]) == 0):
        H, P = x.shape[2], x.shape[3]
        N = B.shape[-1]
        c = min(chunk, x.shape[1])
        vmem = 4 * (H * P * N + c * c * H + 2 * c * H * P)
        if vmem < 12 * 2**20:
            return _ssd.ssd_multihead(x, dt, A, B, C, D, chunk=c,
                                      interpret=interp)
        return _ssd.ssd(x, dt, A, B, C, D, chunk=c, interpret=interp)
    with jax.named_scope("vmemk_ssd"):
        return _ref.ssd_chunked_ref(x, dt, A, B, C, D,
                                    chunk=_best_chunk(x.shape[1], chunk))


def _best_chunk(S: int, chunk: int) -> int:
    c = min(chunk, S)
    while S % c:
        c -= 1
    return max(c, 1)


def ssd_decode(x, dt, A, B, C, D, h):
    """Single-step SSD state update (no kernel needed — pure VPU math)."""
    _note("ssd_decode", False)
    return _ref.ssd_decode_ref(x, dt, A, B, C, D, h)
