"""An architecture module kept with the tests, found as a new file under
``chipbench/arch/`` would be: the dense module's equations with one
published key of its own (``attention_bias``, as in Llama's config),
and a record of every call the benchmark makes into it."""

from arch import dense

#: (function, parameter path or None), in the order of the calls
CALLS = []
KEYS = {**dense.KEYS, "attention_bias": "qkv_bias"}
VALUES = dense.VALUES


def init_std(path, shape, d_model):
    CALLS.append(("init_std", path))
    return dense.init_std(path, shape, d_model)


def shape(config):
    CALLS.append(("shape", None))
    return dense.shape(config)


def tiny(config):
    CALLS.append(("tiny", None))
    return dense.tiny(config)


def logits_at(*args, **kwargs):
    CALLS.append(("logits_at", None))
    return dense.logits_at(*args, **kwargs)


class Spec:
    @staticmethod
    def from_config(config):
        CALLS.append(("Spec.from_config", None))
        return dense.Spec.from_config(config)
