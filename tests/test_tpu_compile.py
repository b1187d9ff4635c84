"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described `v5e:2x2` topology.  That catches what interpret mode
cannot see — block shapes the Mosaic tiling refuses, VMEM overflows — at
phi4-mini-3.8b widths (d_model 3072, 24/8 heads of 128, d_ff 8192, paged
KV blocks of 16).  Nothing runs; each case only has to compile.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.epilogue import Epilogue, Prologue

E, H, KV, D, F = 3072, 24, 8, 128, 8192
B, BS, NB, MB = 4, 16, 256, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_tpu(one_chip):
    """-> compile(fn, *shapes): jit + lower + compile `fn` on one described
    v5e chip with every kernel forced to compiled Pallas.  The persistent
    compile cache is off around these compiles (an entry written here
    cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        structs = [jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), s)
            for s in shapes]
        with ops.kernel_mode("pallas"):
            compiled = jax.jit(fn).lower(*structs).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _weight(k, n, wdt):
    if wdt == "int8":
        return {"q": _s((k, n), jnp.int8), "scale": _s((n,), jnp.float32)}
    return _s((k, n))


def _rms(scale):
    return Prologue(kind="rmsnorm", scale=scale)


@pytest.mark.parametrize("M", [8, 256])
@pytest.mark.parametrize("wdt", ["bfloat16", "int8"])
def test_fused_matmul_compiles(compile_for_tpu, M, wdt):
    def f(x, w, gamma, res):
        return ops.fused_matmul(x, w, prologue=_rms(gamma),
                                epilogue=Epilogue(residual=res),
                                compute_dtype=jnp.bfloat16)
    compile_for_tpu(f, _s((M, E)), _weight(E, H * D, wdt), _s((E,)),
                    _s((M, H * D)))


@pytest.mark.parametrize("M", [8, 256])
@pytest.mark.parametrize("wdt", ["bfloat16", "int8"])
def test_fused_matmul_swiglu_compiles(compile_for_tpu, M, wdt):
    def f(x, wg, wu, gamma):
        return ops.fused_matmul_swiglu(x, wg, wu, prologue=_rms(gamma),
                                       compute_dtype=jnp.bfloat16)
    compile_for_tpu(f, _s((M, E)), _weight(E, F, wdt), _weight(E, F, wdt),
                    _s((E,)))


@pytest.mark.parametrize("op", ["paged_decode_attention",
                                "paged_decode_partials"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles(compile_for_tpu, op, kv_dtype):
    fn = getattr(ops, op)
    pool = _s((2, NB, BS, KV * D), jnp.dtype(kv_dtype))
    args = [_s((B, H, D)), pool, pool, _s((), jnp.int32),
            _s((B, MB), jnp.int32), _s((B,), jnp.int32)]
    if kv_dtype == "int8":
        scales = [_s((2, NB, KV), jnp.float32)] * 2
        compile_for_tpu(lambda q, k, v, i, t, n, ks, vs: fn(
            q, k, v, i, t, n, k_scale=ks, v_scale=vs), *args, *scales)
    else:
        compile_for_tpu(fn, *args)


def test_decode_attention_compiles(compile_for_tpu):
    cache = _s((B, 1024, KV, D))
    compile_for_tpu(ops.decode_attention, _s((B, H, D)), cache, cache,
                    _s((B,), jnp.int32))


def test_flash_attention_compiles(compile_for_tpu):
    compile_for_tpu(ops.flash_attention, _s((1, 512, H, D)),
                    _s((1, 512, KV, D)), _s((1, 512, KV, D)))


def test_rmsnorm_compiles(compile_for_tpu):
    compile_for_tpu(ops.rmsnorm, _s((256, E)), _s((E,)))


def test_residual_rmsnorm_compiles(compile_for_tpu):
    compile_for_tpu(lambda x, y, g: ops.residual_norm(
        x, y, {"scale": g}, "rmsnorm"), _s((256, E)), _s((256, E)), _s((E,)))


# the paged decode step at phi4 (KV 8) and chatglm3 (KV 2) widths, 2 layers:
# a prime block count tells a pool-sized tensor from a weight (no weight
# dimension is a multiple of it), and each pool outweighs the step's
# temporaries (the per-layer weight slices; scalar memory bounds int8's
# block count, see flash_decode._paged_call)
STEP_BLOCKS = {"phi4-mini-3.8b": 4099, "chatglm3-6b": 32749}
MOVES = ("copy", "reshape", "transpose", "dynamic-slice",
         "dynamic-update-slice")


def _moved_pools(hlo: str, nb: int, layer_elems: int) -> list:
    """Instructions of the optimized HLO that copy, relayout, slice or
    update-slice a tensor of at least one layer's pool: by opcode, or by a
    fusion's name (`bitcast_dynamic-update-slice_fusion`, ...)."""
    import re
    out = []
    for m in re.finditer(r"%([\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
                         hlo):
        name, dims, op = m.groups()
        n = 1
        for d in dims.split(","):
            n *= int(d)
        kind = name if op == "fusion" else op
        if (n >= layer_elems and n % nb == 0
                and any(re.search(rf"(^|_){w}(_|\.|-done|$)", kind)
                        for w in MOVES)):
            out.append(f"{name} = {op}[{dims}]")
    return out


@pytest.mark.parametrize("arch", list(STEP_BLOCKS))
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_step_moves_no_pool(compile_for_tpu, topo, arch,
                                         kv_dtype):
    """The decode step, as the engine builds it on one chip, moves no pool
    bytes but the blocks its kernel reads and the rows it writes: no copy,
    relayout, slice or write-back of a pool-sized tensor, and temporaries
    under one pool (the step used to hold a second copy of both)."""
    import dataclasses
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig, uniform_schedule
    from repro.launch import steps
    nb = STEP_BLOCKS[arch]
    cfg = dataclasses.replace(get_config(arch), n_layers=2, vocab=4096,
                              schedule=uniform_schedule("attn", 2))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    step = steps.make_decode_step(
        cfg, ShapeConfig("d", "decode", 1024, 8), mesh, max_seq=1024,
        with_sampling=True, paged=(nb, BS), kv_cache_dtype=kv_dtype)
    with ops.kernel_mode("pallas"):
        compiled = step.fn.lower(*step.in_structs).compile()
    layer_elems = nb * BS * cfg.n_kv_heads * cfg.head_dim
    assert not _moved_pools(compiled.as_text(), nb, layer_elems)
    pool_bytes = 2 * layer_elems * jnp.dtype(kv_dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
