"""Least time of the paged ragged prefill attention kernel's launches
in the traced window (bench/flops.py at the chip's peaks) over its
device time."""
from bench.readers import roofline


def read(rec):
    return roofline(rec, "prefill_attn")
