"""Pallas paged decode attention (kernels/paged_attention.py): its
device time per call against the live keys and values it must read."""

import work
from metrics._kernels import roofline

PATTERN = r"^_paged_kernel$"


def read(run):
    return roofline(run, PATTERN, lambda s: (
        work.paged_attention(run.shape, s["ctx"]) if s["ctx"] else None))
