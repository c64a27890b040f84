"""Pallas flash attention of the prefill chunk (kernels/
flash_attention.py): its device time per call against the causal
attention of the chunk's valid tokens."""

import work
from metrics._kernels import roofline

PATTERN = r"^_kernel$"  # flash_attention._kernel alone


def read(run):
    return roofline(run, PATTERN, lambda s: (
        work.flash_prefill(run.shape, *s["chunk"]) if s["chunk"] else None))
