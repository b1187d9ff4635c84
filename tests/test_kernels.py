"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import decode_attention
from repro.kernels.matmul import matmul, matmul_swiglu
from repro.kernels.rmsnorm import layernorm, rmsnorm
from repro.kernels.ssd import ssd, ssd_multihead

TOL = dict(rtol=2e-2, atol=2e-2)
TOL32 = dict(rtol=2e-5, atol=2e-5)


def _rand(key, shape, dtype):
    return (jax.random.normal(jax.random.key(key), shape) * 0.5).astype(dtype)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Skv,H,KV,D", [
    (1, 128, 128, 4, 4, 64),      # MHA square
    (2, 64, 256, 8, 2, 32),       # GQA, cross lengths
    (1, 200, 200, 4, 1, 64),      # MQA, non-multiple-of-block
    (2, 256, 256, 6, 2, 128),     # 3-way groups
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_oracle(B, Sq, Skv, H, KV, D, dtype):
    q = _rand(0, (B, Sq, H, D), dtype)
    k = _rand(1, (B, Skv, KV, D), dtype)
    v = _rand(2, (B, Skv, KV, D), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = TOL if dtype == jnp.bfloat16 else dict(rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("causal,window", [(False, 0), (True, 0), (True, 32)])
def test_flash_attention_masks(causal, window):
    q = _rand(3, (1, 96, 4, 32), jnp.float32)
    k = _rand(4, (1, 96, 4, 32), jnp.float32)
    v = _rand(5, (1, 96, 4, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_kv=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_q_offset():
    """Sequence-parallel shards: q rows at a positive position offset."""
    q = _rand(6, (1, 32, 2, 32), jnp.float32)
    k = _rand(7, (1, 128, 2, 32), jnp.float32)
    v = _rand(8, (1, 128, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_offset=64,
                          block_q=32, block_kv=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_ref_matches_oracle():
    """The online-softmax scan oracle == full-materialization oracle."""
    q = _rand(9, (2, 64, 4, 32), jnp.float32)
    k = _rand(10, (2, 96, 2, 32), jnp.float32)
    v = _rand(11, (2, 96, 2, 32), jnp.float32)
    a = ref.flash_attention_ref(q, k, v, causal=True, block_kv=32)
    b = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL32)


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,D,window", [
    (2, 128, 4, 4, 64, 0),
    (3, 256, 8, 2, 32, 0),
    (2, 128, 4, 2, 64, 48),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_oracle(B, S, H, KV, D, window, dtype):
    q = _rand(12, (B, H, D), dtype)
    kc = _rand(13, (B, S, KV, D), dtype)
    vc = _rand(14, (B, S, KV, D), dtype)
    length = jnp.array([S - 7, S // 2, 5][:B], jnp.int32)
    out = decode_attention(q, kc, vc, length, window=window, block_kv=64,
                           interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, length, window=window)
    tol = TOL if dtype == jnp.bfloat16 else dict(rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


# --------------------------------------------------------------------------
# tiled GEMM + fused epilogues
# --------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [(128, 256, 128), (100, 100, 60),
                                   (256, 512, 384), (8, 2048, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_vs_oracle(M, K, N, dtype):
    a = _rand(15, (M, K), dtype)
    b = _rand(16, (K, N), dtype)
    out = matmul(a, b, block_m=64, block_n=64, block_k=128, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = TOL if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("activation", ["gelu", "silu"])
def test_matmul_fused_activation(activation):
    a = _rand(17, (64, 128), jnp.float32)
    b = _rand(18, (128, 64), jnp.float32)
    out = matmul(a, b, activation=activation, block_m=32, block_n=32,
                 block_k=64, interpret=True)
    want = ref.matmul_ref(a, b, activation=activation)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_matmul_swiglu_fused():
    a = _rand(19, (64, 128), jnp.float32)
    bg = _rand(20, (128, 96), jnp.float32)
    bu = _rand(21, (128, 96), jnp.float32)
    out = matmul_swiglu(a, bg, bu, block_m=32, block_n=32, block_k=64,
                        interpret=True)
    g = np.asarray(a, np.float32) @ np.asarray(bg, np.float32)
    u = np.asarray(a, np.float32) @ np.asarray(bu, np.float32)
    want = g / (1 + np.exp(-g)) * u
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (2, 17, 128), (1, 7, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_vs_oracle(shape, dtype):
    x = _rand(22, shape, dtype)
    g = _rand(23, shape[-1:], jnp.float32) + 1.0
    out = rmsnorm(x, g, interpret=True)
    want = ref.rmsnorm_ref(x, g)
    tol = TOL if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_layernorm_vs_oracle():
    x = _rand(24, (3, 33, 64), jnp.float32)
    g = _rand(25, (64,), jnp.float32) + 1.0
    b = _rand(26, (64,), jnp.float32)
    out = layernorm(x, g, b, interpret=True)
    want = ref.layernorm_ref(x, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Mamba2 SSD
# --------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 16, 16), (2, 128, 4, 32, 32, 32), (1, 96, 1, 64, 16, 32),
])
def test_ssd_kernel_vs_sequential(B, S, H, P, N, chunk):
    x = _rand(27, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(28, (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(29, (H,), jnp.float32))
    Bm = _rand(30, (B, S, N), jnp.float32)
    Cm = _rand(31, (B, S, N), jnp.float32)
    D = _rand(32, (H,), jnp.float32)
    y, h = ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    y_ref, h_ref = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 16, 16, 16), (2, 128, 4, 32, 32, 32),
])
def test_ssd_multihead_kernel_vs_sequential(B, S, H, P, N, chunk):
    """v2 kernel (all heads per grid cell — B/C streamed once, §Perf P2)."""
    x = _rand(45, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(46, (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(47, (H,), jnp.float32))
    Bm = _rand(48, (B, S, N), jnp.float32)
    Cm = _rand(49, (B, S, N), jnp.float32)
    D = _rand(50, (H,), jnp.float32)
    y, h = ssd_multihead(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    y0, h0 = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h0),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_ref_vs_sequential():
    B, S, H, P, N = 2, 96, 3, 16, 24
    x = _rand(33, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(34, (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(35, (H,), jnp.float32))
    Bm = _rand(36, (B, S, N), jnp.float32)
    Cm = _rand(37, (B, S, N), jnp.float32)
    D = _rand(38, (H,), jnp.float32)
    y1, h1 = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D, chunk=32)
    y0, h0 = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               rtol=2e-4, atol=2e-4)


def test_ssd_decode_step_matches_scan():
    """AR state stepping == one more step of the sequential scan."""
    B, S, H, P, N = 1, 33, 2, 16, 16
    x = _rand(39, (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(40, (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(41, (H,), jnp.float32))
    Bm = _rand(42, (B, S, N), jnp.float32)
    Cm = _rand(43, (B, S, N), jnp.float32)
    D = _rand(44, (H,), jnp.float32)
    y_all, h_prev = ref.ssd_ref(x[:, :-1], dt[:, :-1], A, Bm[:, :-1],
                                Cm[:, :-1], D)
    y_t, h_t = ref.ssd_decode_ref(x[:, -1], dt[:, -1], A, Bm[:, -1],
                                  Cm[:, -1], D, h_prev)
    y_full, h_full = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_full[:, -1]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_t), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


def test_dispatch_record_names_tpu_fallbacks(monkeypatch):
    """On a TPU backend every auto-mode dispatch is counted by op name: a
    reference taken by an op that has a kernel is a hidden fallback, the
    reference-only ops are not; forced modes and other backends are never
    counted.  (eval_shape traces the dispatch without compiling.)"""
    from repro.kernels import ops
    def matmul(a, b):        # a fresh function per call: no trace cache
        return jax.eval_shape(lambda a, b: ops.matmul(a, b), a, b)

    a2 = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    a3 = jax.ShapeDtypeStruct((2, 8, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ops.reset_dispatch_record()
    matmul(a3, b)                  # CPU: not counted
    assert ops.dispatch_record() == {}

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    with ops.kernel_mode("auto"):
        matmul(a2, b)              # Pallas
        matmul(a3, b)              # 3-D: reference
        jax.eval_shape(lambda *a: ops.ssd_decode(*a),
                       *[jax.ShapeDtypeStruct(s, jnp.float32) for s in
                         [(1, 2, 4), (1, 2), (2,), (1, 3), (1, 3), (2,),
                          (1, 2, 4, 3)]])
    with ops.kernel_mode("ref"):
        matmul(a2, b)              # forced: not counted
    try:
        assert ops.dispatch_record() == {
            "matmul": {"pallas": 1, "ref": 1},
            "ssd_decode": {"pallas": 0, "ref": 1}}
        assert ops.hidden_fallbacks() == ["matmul"]
    finally:
        ops.reset_dispatch_record()


def test_seq_sp_steps_on_one_device_dispatch_no_reference(monkeypatch):
    """phi4's seq_sp serve steps on a one-device mesh: the sequence offset
    is a static 0, so prefill attention takes the flash kernel, and no op
    with a kernel takes the reference (traced as if on TPU; eval_shape
    traces without compiling)."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core import collectives as col
    from repro.kernels import ops
    from repro.launch import steps
    from repro.launch.mesh import make_mesh_for
    assert col.axis_index(()) == 0
    cfg = get_config("phi4-mini-3.8b").reduced()
    mesh = make_mesh_for(1)
    prefill = steps.make_prefill_step(
        cfg, ShapeConfig("p", "prefill", 16, 2), mesh, max_seq=64,
        with_sampling=True, compact_kv=True)
    decode = steps.make_decode_step(
        cfg, ShapeConfig("d", "decode", 64, 2), mesh, max_seq=64,
        with_sampling=True, paged=(8, 16))
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.reset_dispatch_record()
    try:
        for step in (prefill, decode):
            jax.eval_shape(step.fn, *step.in_structs)
        rec = ops.dispatch_record()
        assert rec["flash_attention"] == {"pallas": 1, "ref": 0}
        assert rec["paged_decode_partials.stacked"]["pallas"] == 1
        assert ops.hidden_fallbacks() == []
    finally:
        ops.reset_dispatch_record()
