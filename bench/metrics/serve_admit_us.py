"""Median µs of the program's ``repro.serve.admit`` span over the traced
window, per serving wave: the frames' host-to-device copies, the stack and
the zero padding."""
from bench.metrics import program_spans

SPAN = "repro.serve.admit"


def read(obs):
    return program_spans.p50_us(SPAN)


def describe(obs) -> str:
    return program_spans.describe(SPAN)
