"""Compile the serving path's Pallas kernels for a described TPU v5e chip
at qwen2-0.5b widths (14 heads, 2 kv heads, head_dim 64, bf16, 128-token
pages).  Nothing runs: the TPU compiler rejects here what the chip would
reject (block shapes off the (8, 128) tiling, values captured by a kernel).

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS, ShapeConfig
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    decode_attention_pallas,
    paged_attention_pallas,
)
from repro.models import Model

H, HKV, D, BLOCK = 14, 2, 64, 128
SLOTS, MAX_SEQ = 8, 4096
N_POOL = MAX_SEQ // BLOCK + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep it out of the way while they run
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compiled_text(fn, *avals) -> str:
    return jax.jit(fn).lower(*avals).compile().as_text()


@pytest.mark.parametrize("global_pages", [True, False])
def test_paged_attention_compiles(one_chip, global_pages):
    pool = spec(one_chip, (SLOTS, N_POOL, HKV, BLOCK, D))
    text = compiled_text(
        functools.partial(paged_attention_pallas, n_kv=16,
                          global_pages=global_pages),
        spec(one_chip, (SLOTS, H, D)), pool, pool,
        spec(one_chip, (SLOTS, N_POOL), jnp.int32),
        spec(one_chip, (SLOTS,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_flash_attention_traced_q_offset_compiles(one_chip):
    kv = spec(one_chip, (1, 8 * BLOCK, HKV, D))
    text = compiled_text(
        lambda q, k, v, off: flash_attention_pallas(q, k, v, causal=True,
                                                    q_offset=off),
        spec(one_chip, (1, BLOCK, H, D)), kv, kv,
        spec(one_chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(one_chip):
    kv = spec(one_chip, (SLOTS, MAX_SEQ, HKV, D))
    text = compiled_text(
        decode_attention_pallas, spec(one_chip, (SLOTS, H, D)), kv, kv,
        spec(one_chip, (SLOTS,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("entry", ["decode_step", "prefill_chunk"])
def test_served_model_entry_compiles_with_kernels(one_chip, entry,
                                                  monkeypatch):
    """The whole qwen2-0.5b decode step / prefill chunk at published
    widths, with the Pallas kernels selected (this process's backend is
    the CPU, so the test asks for them by name)."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    model = Model(ARCHS["qwen2-0.5b"])

    def place(tree):
        return jax.tree.map(lambda x: spec(one_chip, x.shape, x.dtype), tree)

    params = place(model.abstract_params())
    cache = place(jax.eval_shape(lambda: model.init_cache(
        ShapeConfig("serve", "decode", MAX_SEQ, SLOTS))))
    i32 = functools.partial(spec, one_chip, dtype=jnp.int32)
    if entry == "decode_step":
        fn = functools.partial(model.decode_step, n_kv=8, global_pages=True)
        batch = {"tokens": i32((SLOTS, 1)), "lengths": i32((SLOTS,)),
                 "block_table": i32((SLOTS, N_POOL))}
    else:
        fn = functools.partial(model.prefill_chunk, n_kv=8,
                               global_pages=True)
        batch = {"tokens": i32((1, BLOCK)), "start": i32(()),
                 "slot": i32(()), "row": i32((N_POOL,)),
                 "pages": i32((1,)), "last_index": i32(())}
    text = compiled_text(lambda p, c, b: fn(p, c, b)[0], params, cache,
                         batch)
    assert "tpu_custom_call" in text
