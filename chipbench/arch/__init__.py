"""One module per architecture the benchmark serves, named by a
configuration file's ``"bench_arch"`` and found by that name, as the
per-layer readers are found in ``metrics/``.  Everything the benchmark
knows of an architecture is in its module:

* ``KEYS``: published key -> the program's ``ModelConfig`` field, for
  the keys beyond the shared ``harness.MODEL_KEYS``; each is applied and
  checked as those are.
* ``VALUES``: published key -> the one value the module's equations
  cover (``hidden_act``, say), checked where the file gives the key.
* ``init_std(path, shape, d_model) -> float | None``: the std of a
  parameter's random values (``weights``), asked first; ``None`` leaves
  the name to the shared rules.
* ``Spec.from_config(config)`` and ``logits_at(weights, spec, tokens,
  rows, *, precision, whole_blocks)``: the plain float32 reference that
  decides ``correct`` (``check``), with its float8 control
  (``precision="fp8"``).
* ``shape(config) -> work.Shape``: the sizes and the matrix parameters
  per token that the work counts read.
* ``tiny(config) -> dict``: the keys the CPU tests' cut sets beyond the
  shared widths (experts held, a window).

A configuration file accounts for every key it gives: a shared or a
module key, a value the module checks, a bookkeeping key
(``harness.BOOKKEEPING``) or a key named under ``assumed``.
"""
