"""Compile the serving path's Pallas kernels for a described TPU v5e chip
at qwen2-0.5b widths (14 heads, 2 kv heads, head_dim 64, bf16, 128-token
pages).  Nothing runs: the TPU compiler rejects here what the chip would
reject (block shapes off the (8, 128) tiling, values captured by a kernel).
Each custom call the compiler emits carries its kernel's stable name
(``pallas_call(name=...)``), the name its events have in a profile.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS, ShapeConfig
from repro.kernels.block_gather import block_gather_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    decode_attention_pallas,
    paged_attention_pallas,
)
from repro.models import Model
from repro.serving.device_state import DeviceState

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


def kernels(text: str) -> set:
    """The names of the compiled program's Pallas custom calls, without
    the numeric suffix the compiler adds."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_][\w-]*?)(?:\.\d+)* = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text)}


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
    assert kernels(text) == {"paged_attention"}


def test_flash_attention_traced_q_offset_compiles(one_chip):
    kv = spec(one_chip, (1, 8 * BLOCK, HKV, D))
    text = compiled_text(
        lambda q, k, v, off: flash_attention_pallas(q, k, v, causal=True,
                                                    q_offset=off),
        spec(one_chip, (1, BLOCK, H, D)), kv, kv,
        spec(one_chip, (), jnp.int32),
    )
    assert kernels(text) == {"flash_attention"}


def test_decode_attention_compiles(one_chip):
    kv = spec(one_chip, (SLOTS, MAX_SEQ, HKV, D))
    text = compiled_text(
        decode_attention_pallas, spec(one_chip, (SLOTS, H, D)), kv, kv,
        spec(one_chip, (SLOTS,), jnp.int32),
    )
    assert kernels(text) == {"decode_attention"}


def test_block_gather_compiles(one_chip):
    text = compiled_text(
        block_gather_pallas, spec(one_chip, (N_POOL, HKV, BLOCK, D)),
        spec(one_chip, (4,), jnp.int32),
    )
    assert kernels(text) == {"block_gather"}


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
    assert kernels(text) == {
        "decode_step": {"paged_kv_write", "paged_attention"},
        "prefill_chunk": {"paged_kv_write", "block_gather",
                          "flash_attention"}}[entry]


def test_fused_step_updates_the_pool_in_place(one_chip, monkeypatch):
    """The engine's fused step at qwen2-0.5b widths, with a prefill chunk
    riding it at ``n_kv`` 1 (the step that asks for the most
    temporaries): no ``copy`` or ``broadcast`` has the shape of the
    stacked pool or of one layer's slab, in either tile order, and the
    temporaries stay below one K+V pool."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    model = Model(ARCHS["qwen2-0.5b"])
    slots = 16
    cache = jax.eval_shape(lambda: model.init_cache(
        ShapeConfig("serve", "decode", MAX_SEQ, slots)))
    dev = DeviceState(model, model.abstract_params(), cache,
                      max_slots=slots, mb=N_POOL, block=BLOCK,
                      temperature=0.0, top_p=1.0, chunk_tokens=BLOCK,
                      global_pages=True)
    operands = jax.tree.map(
        lambda x: spec(one_chip, jnp.shape(x), jnp.result_type(x)),
        dev._operands({}, {}))
    step = jax.jit(dev._step_fn, donate_argnums=(1, 3, 4, 5, 6, 8),
                   static_argnums=(29, 30))
    compiled = step.lower(*operands, 1, True).compile()

    pool = cache["layers"]["k_pool"]
    L, S, N = pool.shape[:3]
    tile = pool.shape[3:]
    stacks = {(L, S, N) + tile, (L, S * N) + tile}
    slabs = {s[1:] for s in stacks}
    shapes = {s[:-2] + t for s in stacks | slabs
              for t in (s[-2:], s[-2:][::-1])}
    moved = [m.group(0) for m in re.finditer(
        r"%\S+ = \w+\[([\d,]*)\]\S* (?:copy|broadcast)\(",
        compiled.as_text())
        if tuple(int(d) for d in m.group(1).split(",") if d) in shapes]
    assert moved == []
    pool_bytes = 2 * pool.size * pool.dtype.itemsize  # K and V
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
