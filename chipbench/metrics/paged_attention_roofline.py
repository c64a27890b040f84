"""Pallas paged decode attention, found in the trace by its stable
kernel name ``paged_attention`` (kernels/paged_attention.py): its device
time per call against the live keys and values it must read."""

import work
from metrics._kernels import roofline

PATTERN = r"^%?paged_attention$"


def read(run):
    return roofline(run, PATTERN, lambda s: (
        work.paged_attention(run.shape, s["ctx"]) if s["ctx"] else None))
