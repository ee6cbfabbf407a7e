"""Median µs of the program's ``repro.call.launch`` span over the traced
window, per filter call: the jitted executable's dispatch, up to the future
it returns."""
from bench.metrics import program_spans

SPAN = "repro.call.launch"


def read(obs):
    return program_spans.p50_us(SPAN)


def describe(obs) -> str:
    return program_spans.describe(SPAN)
