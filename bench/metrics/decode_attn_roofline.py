"""Least time of the paged decode attention kernel's launches in the
traced window (bench/flops.py at the chip's peaks) over its device time;
decode attention is bound by memory."""
from bench.readers import roofline


def read(rec):
    return roofline(rec, "decode_attn")
