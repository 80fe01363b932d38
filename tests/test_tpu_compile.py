"""Every registered kernel, and one full-width serving step, compiled for a
described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: unaligned blocks,
scalar stores to VMEM, missing lowerings, programs that do not fit.  These
compiles catch that at no chip time.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests and
only the worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import api
from repro.models import build_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(sh):
    """op name -> [(label, fn, arg specs)] at the widths the repo serves."""
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    mm = (4096, 4096)
    qkv = (1, 2048, 40, 128)  # Qwen2.5-14B heads at a 2048-token context
    # zamba2-7b SSD widths: 112 heads of 64, state 64, chunk 256
    u, a, bc = (1, 1024, 112, 64), (1, 1024, 112), (1, 1024, 64)
    row = (1024, 2048)
    return {
        "axpy": [("f32", lambda x, y: api.axpy(x, y, 2.0, backend="pallas", interpret=False),
                  [_spec(row, f32, sh)] * 2)],
        "stream_copy": [("f32", lambda x: api.stream_copy(x, backend="pallas", interpret=False),
                         [_spec(row, f32, sh)])],
        "stream_reduce": [("f32", lambda x: api.stream_reduce(x, backend="pallas",
                                                              interpret=False),
                           [_spec(row, f32, sh)])],
        "strided_reduce": [("stride4", lambda x: api.strided_reduce(
            x, stride=4, backend="pallas", interpret=False), [_spec(row, f32, sh)])],
        "pchase": [("64k", lambda p: api.pchase(p, 1024, backend="pallas", interpret=False),
                    [_spec((1 << 16,), jnp.int32, sh)])],
        "matmul": [
            ("bf16", lambda x, y: api.matmul(x, y, backend="pallas", interpret=False),
             [_spec(mm, bf16, sh)] * 2),
            ("int8->int32", lambda x, y: api.matmul(x, y, out_dtype=jnp.int32,
                                                    backend="pallas", interpret=False),
             [_spec(mm, i8, sh)] * 2),
        ],
        "flash_attention": [("bf16 causal", lambda q, k, v: api.flash_attention(
            q, k, v, causal=True, backend="pallas", interpret=False),
            [_spec(qkv, bf16, sh)] * 3)],
        "ssm_scan": [("bf16", lambda u_, a_, b_, c_: api.ssm_scan(
            u_, a_, b_, c_, chunk=256, backend="pallas", interpret=False),
            [_spec(u, bf16, sh), _spec(a, f32, sh), _spec(bc, bf16, sh), _spec(bc, bf16, sh)])],
    }


def test_every_registered_op_has_a_compile_case():
    assert set(api.op_names()) == set(_kernel_cases(None))


@pytest.mark.parametrize("op_name", api.op_names())
def test_kernel_compiles_for_v5e(one_chip, op_name):
    for label, fn, specs in _kernel_cases(one_chip)[op_name]:
        compiled = jax.jit(fn).lower(*specs).compile()
        assert "tpu_custom_call" in compiled.as_text(), f"{op_name} {label}: no kernel"


def test_qwen_paged_prefill_step_compiles_for_v5e(one_chip):
    """Qwen2.5-14B at full width, 2 layers, bf16: one ``decode_chunk_paged``
    of 8 lanes x 16 tokens over a 1024-page pool fits the chip."""
    cfg = get_config("qwen2.5-14b").replace(n_layers=2, param_dtype="bfloat16")
    model = build_model(cfg)

    def placed(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = placed(jax.eval_shape(lambda: model.init(jax.random.key(0))))
    pool = placed(model.paged_cache_specs(1024, 16))
    table = _spec((8, 128), jnp.int32, one_chip)
    chunk = _spec((8, 16), jnp.int32, one_chip)
    compiled = jax.jit(model.decode_chunk_paged).lower(
        params, pool, table, chunk, chunk).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9
