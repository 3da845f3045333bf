"""The model FLOPs of the work handed to the engine in the traced
window over the window times the chip's bf16 peak (bench/flops.py)."""
from bench.readers import step_mfu


def read(rec):
    return step_mfu(rec)
