"""Compile each cell's fused serving step at its real size for a
described TPU v5e (nothing runs): the chip's compiler refuses here what
the chip would refuse, and ``memory_analysis`` says whether the cell's
slots fit in 16 GB.

The topology is described inside a module fixture, never at import:
only the worker that runs this file loads the TPU compiler."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import _paths  # noqa: F401
import harness

#: what XLA may allocate of a v5e chip's 16 GB (its own out-of-memory report)
HBM_USABLE = 15.75e9
MARGIN = 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep it out of the way while they run
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def fused_step(cell_name, sharding, n_kv):
    """The engine's fused step for ``cell_name`` at its real size, with a
    prefill chunk riding it, compiled for ``sharding``'s chip."""
    from repro.models import Model
    from repro.models.transformer import BLOCK_SIZE
    from repro.serving.device_state import DeviceState
    from repro.configs import ShapeConfig

    cell = harness.load_cell(cell_name)
    cfg = harness.model_config(cell.config)
    model = Model(cfg)
    kw = cell.engine_kw
    slots, max_seq = kw["max_slots"], kw["max_seq"]
    cache = jax.eval_shape(lambda: model.init_cache(
        ShapeConfig("engine", "decode", max_seq, slots)))
    s = cell.mix["sampling"]
    dev = DeviceState(model, model.abstract_params(), cache,
                      max_slots=slots, mb=-(-max_seq // BLOCK_SIZE) + 1,
                      block=BLOCK_SIZE, temperature=s["temperature"],
                      top_p=s["top_p"], chunk_tokens=kw["chunk_tokens"],
                      global_pages=True)
    ops = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding),
        dev._operands({}, {}))
    step = jax.jit(dev._step_fn, donate_argnums=(1, 3, 4, 5, 6, 8),
                   static_argnums=(29, 30))
    return step.lower(*ops, n_kv, True).compile()


# n_kv 1 with a chunk riding the step (a first chunk while no slot
# holds more than one page) asks for the most temporaries: about three
# KV pools' worth
@pytest.mark.parametrize("cell_name,n_kv", [
    ("qwen2-0.5b.chat", 32),
    ("qwen2-0.5b.chat", 1),
    ("phi3-mini-4k.l8.batch", 16),
    ("phi3-mini-4k.l8.batch", 1),
])
def test_cell_fused_step_compiles_and_fits(one_chip, cell_name, n_kv,
                                           monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    compiled = fused_step(cell_name, one_chip, n_kv)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"{cell_name} n_kv={n_kv}: arguments "
          f"{mem.argument_size_in_bytes} outputs {mem.output_size_in_bytes}"
          f" aliased {mem.alias_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} total {total}")
    # a margin for what the process holds beside the step (the tokens
    # of in-flight steps, the allocator's fragmentation)
    assert total + MARGIN < HBM_USABLE
