"""Median µs of the program's ``repro.serve.copy_out`` span over the traced
window, per serving wave: the wait for the device and the copy of the
padded batch to the host."""
from bench.metrics import program_spans

SPAN = "repro.serve.copy_out"


def read(obs):
    return program_spans.p50_us(SPAN)


def describe(obs) -> str:
    return program_spans.describe(SPAN)
