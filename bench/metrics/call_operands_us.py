"""Median µs of the program's ``repro.call.operands`` span over the traced
window, per filter call: the shape and dtype checks and the
coefficient and gain operands, with any host-to-device upload."""
from bench.metrics import program_spans

SPAN = "repro.call.operands"


def read(obs):
    return program_spans.p50_us(SPAN)


def describe(obs) -> str:
    return program_spans.describe(SPAN)
