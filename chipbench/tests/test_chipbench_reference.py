"""The plain reference against the served program at a tiny size on the
CPU: the program's own forward pass, and the tokens it serves through
chunked prefill and cached decode, agree with the reference; the
float8 control does not."""

import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import check
import harness
import traffic
import weights as W
from arch import dense
from _tiny import tiny

SEED = 2**32 + 9


def _cell(name, dtype="float32"):
    cell = harness.load_cell(name)
    tiny(cell, dtype=dtype)
    if name.startswith("phi3"):  # keep it multi-head, as published
        cell.config["num_key_value_heads"] = 4
    return cell


@pytest.mark.parametrize("name", ["qwen2-0.5b.chat",
                                  "phi3-mini-4k.l8.batch"])
def test_reference_matches_the_programs_forward_pass(name):
    from repro.models import Model

    cell = _cell(name)
    cfg = harness.model_config(cell.config)
    model = Model(cfg)
    w = W.make_weights(model.abstract_params(), SEED, cfg.d_model,
                       dense.init_std)
    toks = np.random.default_rng(0).integers(1, 512, 300)
    got, _ = model.prefill(w, {"tokens": jnp.asarray(toks)[None]})
    want = dense.logits_at(w, dense.Spec.from_config(cell.config), toks,
                           [299])
    assert np.abs(np.asarray(got[0]) - np.asarray(want[0])).max() < 1e-4


def _serve(cell, n=6, seconds=4.0):
    cfg = harness.model_config(cell.config)
    items = traffic.generate(cell.mix, SEED, seconds, cfg.vocab_size, 2.0)
    items = sorted(items, key=lambda it: -len(it.prompt))[:n]
    _, eng = harness.build_engine(cell, SEED)
    reqs = [eng.submit(it.prompt, max_new_tokens=it.max_new,
                       sample_key=it.sample_key) for it in items]
    while not all(r.done for r in reqs):
        eng.step()
    assert eng.stats()["prefill_chunks"] > n  # prompts took several chunks
    return [(it.prompt, list(map(int, r.generated)), it.sample_key)
            for it, r in zip(items, reqs)]


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_served_tokens_agree_with_the_reference(temperature):
    cell = _cell("qwen2-0.5b.chat")
    cell.mix["sampling"].update(temperature=temperature, top_p=0.9)
    served = _serve(cell)
    r = check.widest(cell, SEED, served, temperature, 0.9)
    assert r["tokens"] > 30
    assert r["widest"] < 1e-4


def test_float8_control_reads_far_above_the_bfloat16_program():
    cell = _cell("qwen2-0.5b.chat", dtype="bfloat16")
    cell.mix["sampling"]["temperature"] = 0.0
    cell.mix["output"].update(median=24, min=24, max=24)
    served = _serve(cell)
    program = check.widest(cell, SEED, served, 0.0, 1.0)
    control = check.widest(cell, SEED, served, 0.0, 1.0, precision="fp8")
    assert control["widest"] > 3 * program["widest"]
