"""A cell cut to a size a CPU test can run: the same code, two layers
of width 128 and a 512-token vocabulary, four slots, short prompts; the
configuration's architecture module cuts what is its own."""


def tiny(cell, dtype="float32", limit=1e-3):
    c = cell.config
    c.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
             torch_dtype=dtype)
    if "head_dim" in c:
        c["head_dim"] = 32
    c.update(cell.arch.tiny(c))
    c["engine"].update(max_slots=4, max_seq=512)
    m = cell.mix
    for k in ("prompt", "user"):
        if k in m:
            m[k].update(median=40, max=200, min=8)
    m["output"].update(median=8, max=24, min=2)
    m["max_total"] = 500
    if "system_prompts" in m:
        m["system_prompts"].update(tokens=130, count=3)
    if "requests_per_s_max" in m:
        m["requests_per_s_max"] = 5
    if "rate_per_s" in cell.cell:
        cell.cell["rate_per_s"] = 2.0
    cell.cell["check"]["limit"] = limit


def as_cell(name):
    """``(cell, config_override)`` for ``run_cell``: the tiny cut of the
    cell ``name``.  A cell not in BENCHMARK.json is one whose data files
    are kept for a later PR (``chipbench/cells/<name>.json`` names its
    traffic mix and the cell it shares its configuration with)."""
    import json

    import harness
    import traffic

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    if any(w["name"] == name for w in bench["workloads"]):
        return name, tiny
    data = json.loads((harness.HERE / "cells" / f"{name}.json").read_text())

    def deferred(cell):
        cell.name = name
        cell.mix = traffic.load_mix(data["traffic"])
        cell.cell = data
        tiny(cell)

    return data["like"], deferred
