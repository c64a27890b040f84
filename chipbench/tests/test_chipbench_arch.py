"""The seam between the benchmark and an architecture
(``chipbench/arch/<bench_arch>.py``): the dense module counts and checks
what the benchmark counted and checked before it had the seam; a new
architecture arrives as a new module, found by its name; a published
``head_dim`` is served and counted; and a configuration that names no
module, a missing one, or a key nothing accounts for is refused at
load, as is a parameter no rule initialises."""

import importlib
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import check
import harness
import weights as W
import work
from _tiny import tiny
from arch import dense

HERE = Path(__file__).resolve().parent
SEED = 2**32 + 9
CTX = [100, 300, 4000]

# ---------------------------------------------------------------------------
# the dense cells read what they read before the seam
# ---------------------------------------------------------------------------
#: work.Shape.from_config's counts on the parent of the seam, for both
#: published configurations: step_flops at CTX without and with the
#: chunk (128, 256), paged_attention at CTX, flash_prefill(128, 256)
COUNTS = {
    "qwen2-0.5b": (3342237696, 97337540608, (15769600, 2263552),
                   (88309760, 589824)),
    "phi3-mini-4k.l8": (6459359232, 241006804992, (54067200, 54104064),
                        (302776320, 4718592)),
}


def _config(name):
    return json.loads((harness.HERE / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_dense_counts_are_those_before_the_seam(name):
    s = dense.shape(_config(name))
    step, step_chunk, paged, flash = COUNTS[name]
    assert work.step_flops(s, CTX) == step
    assert work.step_flops(s, CTX, (128, 256)) == step_chunk
    assert work.paged_attention(s, CTX) == paged
    assert work.flash_prefill(s, 128, 256) == flash


#: check.widest on the parent of the seam, over SERVED at the tiny cut
#: (CPU, float32 weights from SEED): the program's number and the
#: float8 control's
WIDEST = {
    ("qwen2-0.5b.chat", "f32"): 9.534093856811523,
    ("qwen2-0.5b.chat", "fp8"): 0.0,
    ("phi3-mini-4k.l8.batch", "f32"): 11.113752365112305,
    ("phi3-mini-4k.l8.batch", "fp8"): 0.5443310737609863,
}


def _served():
    rng = np.random.default_rng(5)
    return [(rng.integers(1, 512, p).tolist(),
             rng.integers(1, 512, n).tolist(), 0)
            for p, n in ((40, 8), (130, 3), (7, 12))]


@pytest.mark.parametrize("cell,precision", sorted(WIDEST))
def test_dense_check_reads_what_it_read_before_the_seam(cell, precision):
    c = harness.load_cell(cell)
    tiny(c)
    r = check.widest(c, SEED, _served(), 0.0, 1.0, precision=precision)
    assert r["tokens"] == 23
    assert r["widest"] == WIDEST[(cell, precision)]


# ---------------------------------------------------------------------------
# a new architecture is new files: the probe module, found by its name
# ---------------------------------------------------------------------------
@pytest.fixture
def probe(monkeypatch):
    import arch

    monkeypatch.setattr(arch, "__path__", [*arch.__path__, str(HERE)])
    mod = importlib.import_module("arch.probe_arch")
    mod.CALLS.clear()
    return mod


def test_each_part_of_an_architecture_comes_from_its_module(probe):
    config = harness.load_cell("qwen2-0.5b.chat").config
    config["attention_bias"] = False
    with pytest.raises(ValueError, match="'attention_bias'"):
        harness.model_config(config)  # dense maps no such key
    config["bench_arch"] = "probe_arch"
    assert harness.model_config(config).qkv_bias is False

    def override(cell):
        cell.config.update(bench_arch="probe_arch", attention_bias=False)
        tiny(cell)

    r = harness.run_cell("qwen2-0.5b.chat", SEED, 3.0, False,
                         chips_required=False, config_override=override)
    assert r["correct"] is True, r["compared"]
    called = {f for f, _ in probe.CALLS}
    assert called == {"tiny", "init_std", "Spec.from_config", "logits_at",
                      "shape"}
    # the probe's key reached the program: a model without q/k/v biases
    paths = {p for f, p in probe.CALLS if f == "init_std"}
    assert "layers/attn/wq" in paths
    assert not any(p.endswith(("bq", "bk", "bv")) for p in paths)


# ---------------------------------------------------------------------------
# a published head_dim
# ---------------------------------------------------------------------------
def test_a_published_head_dim_is_served_and_counted():
    def override(cell):
        tiny(cell)
        cell.config["head_dim"] = 48  # hidden_size / heads is 32

    r = harness.run_cell("qwen2-0.5b.chat", SEED, 3.0, False,
                         chips_required=False, config_override=override)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["tokens_compared"]["value"] > 0
    cell = harness.load_cell("qwen2-0.5b.chat")
    override(cell)
    assert harness.model_config(cell.config).resolved_head_dim == 48
    assert dense.Spec.from_config(cell.config).head_dim == 48
    s = cell.arch.shape(cell.config)
    assert s.head_dim == 48
    assert work.paged_attention(s, [10])[0] == 4 * 4 * 48 * 10


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------
FILE = "chipbench/configs/qwen2-0.5b.json"


@pytest.mark.parametrize("edit,named", [
    (lambda c: c.pop("bench_arch"), "bench_arch"),
    (lambda c: c.update(bench_arch="no_such_arch"), "no_such_arch"),
    (lambda c: c.update(num_experts_per_tok=8), "num_experts_per_tok"),
    (lambda c: c.update(hidden_act="gelu"), "hidden_act"),
])
def test_a_configuration_is_refused_at_load(tmp_path, edit, named):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    config = json.loads((harness.ROOT / FILE).read_text())
    edit(config)
    (tmp_path / FILE).parent.mkdir(parents=True)
    (tmp_path / FILE).write_text(json.dumps(config))
    with pytest.raises(ValueError) as e:
        harness.load_cell("qwen2-0.5b.chat", tmp_path)
    assert FILE in str(e.value) and named in str(e.value)


def test_both_published_configurations_load():
    for cell in ("qwen2-0.5b.chat", "phi3-mini-4k.l8.batch"):
        c = harness.load_cell(cell)
        assert c.arch is dense
        harness.model_config(c.config)


def test_a_parameter_no_rule_names_is_refused():
    abstract = {"layers": {"moe": {"router": jax.ShapeDtypeStruct(
        (2, 128, 8), jnp.float32)}}}
    with pytest.raises(ValueError, match="layers/moe/router"):
        W.make_weights(abstract, 1, 128, dense.init_std)
    w = W.make_weights(abstract, 1, 128, lambda path, shape, d: (
        0.02 if path == "layers/moe/router" else None))
    assert float(jnp.std(w["layers"]["moe"]["router"])) == pytest.approx(
        0.02, rel=0.1)
