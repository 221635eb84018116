"""Compile the main-path kernels for a described TPU v5e, without a chip.

Interpret-mode parity (test_kernels.py) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, 8-bit compares, unsigned
reductions, VMEM overruns.  These tests lower each kernel with
``interpret=False`` for one device of a described ``v5e:2x2`` topology at
olmo-1b widths and the dump pipeline's grids, compile it, and require the
Mosaic kernel (``tpu_custom_call``) in the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker imports
this file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

CHUNK = 64 * 1024        # DeltaCR's default chunk_bytes
KV_ROW = 1 << 20         # one olmo-1b KV page row: 16 layers x 16 x 16 x 128 bf16
POOL = (2048, 16, 16, 128)


def _kernel(name):
    return importlib.import_module(f"repro.kernels.{name}")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_native(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_paged_attention_compiles(spec):
    pa = _kernel("paged_attention")
    bf = jnp.bfloat16
    _compile_native(
        lambda q, k, v, t, n: pa.paged_attention(q, k, v, t, n),
        spec((4, 16, 1, 128), bf), spec(POOL, bf), spec(POOL, bf),
        spec((4, 64), jnp.int32), spec((4,), jnp.int32),
    )


@pytest.mark.parametrize("rows,row_bytes", [(64, CHUNK), (64, KV_ROW), (4, 8 * KV_ROW)])
def test_delta_diff_compiles(spec, rows, row_bytes):
    dd = _kernel("delta_diff")
    g = spec((rows, row_bytes), jnp.uint8)
    _compile_native(lambda a, b: dd.delta_diff(a, b), g, g)


@pytest.mark.parametrize("rows,row_bytes", [(64, CHUNK), (64, KV_ROW), (4, 8 * KV_ROW)])
def test_delta_fused_compiles(spec, rows, row_bytes):
    df = _kernel("delta_fused")
    g = spec((rows, row_bytes), jnp.uint8)
    _compile_native(lambda a, b: df.delta_fused(a, b, max_changed=rows // 2), g, g)


@pytest.mark.parametrize("rows,row_bytes", [(64, CHUNK), (64, KV_ROW)])
def test_delta_apply_compiles(spec, rows, row_bytes):
    da = _kernel("delta_apply")
    _compile_native(
        lambda b, d, i: da.delta_apply(b, d, i),
        spec((rows, row_bytes), jnp.uint8), spec((8, row_bytes), jnp.uint8),
        spec((8,), jnp.int32),
    )


def test_page_copy_stacked_compiles(spec):
    pc = _kernel("page_copy")
    _compile_native(
        lambda p, s, d: pc.page_copy_stacked(p, s, d),
        spec((16,) + POOL, jnp.bfloat16), spec((4,), jnp.int32), spec((4,), jnp.int32),
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_shard_block_encode_compiles(spec, dtype):
    # one shard of a (16384, 32768) state on a (2, 2) mesh, under the
    # TilePlan of shard_dump (32 x 32 tiles of the global array)
    td = _kernel("tile_diff")
    blk = spec((8192, 16384), dtype)
    counts, tile = (16, 16), (512, 1024)
    assert td.kernel_fits(tile, dtype)
    _compile_native(
        lambda a, b: td.block_encode(a, b, counts, tile, 64, use_kernel=True),
        blk, blk,
    )


def test_olmo_decode_step_compiles(spec, monkeypatch):
    """The served decode step at olmo-1b widths reaches the native kernel
    and fits one chip's memory with a 2048-page pool."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import Model

    # ops routes by the default backend, which is the CPU here
    monkeypatch.setattr(ops, "_use_kernel", lambda: True)
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    model = Model(get_config("olmo-1b"))
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype), model.param_shapes())
    B, maxp = 4, 64
    pool = spec((16,) + POOL, jnp.bfloat16)
    cache = {
        "lens": spec((B,), jnp.int32),
        "stage0": {
            "l0_s0_attn": {"pk": pool, "pv": pool, "table": spec((16, B, maxp), jnp.int32)}
        },
    }
    compiled = jax.jit(model.decode_step).lower(params, spec((B,), jnp.int32), cache).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert peak < 15 * 2**30, peak
