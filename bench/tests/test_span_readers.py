"""The readers of the program's spans and copy counters, on a hand-built
registry and observation: each reads its number, and None where the
program recorded nothing (as a program without the spans does)."""
import pytest

from bench import generator
from bench.metrics import (call_launch_us, call_operands_us,
                           serve_admit_us, serve_copy_out_us,
                           serve_d2h_bytes_per_frame)
from repro import obs

SPAN_READERS = [(call_operands_us, "repro.call.operands"),
                (call_launch_us, "repro.call.launch"),
                (serve_admit_us, "repro.serve.admit"),
                (serve_copy_out_us, "repro.serve.copy_out")]


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.disable()
    obs.REGISTRY.reset()
    yield
    obs.REGISTRY.reset()


def _obs(engine=None):
    return generator.Observation(window_s=1.0, pixels_done=0.0, attempted=0,
                                 failed=0, engine=engine)


@pytest.mark.parametrize("reader,span", SPAN_READERS)
def test_span_reader_is_the_median_of_its_span(reader, span):
    for us in (30.0, 10.0, 20.0, 500.0, 40.0):
        obs.REGISTRY.histogram("span/" + span).record(us)
    obs.REGISTRY.histogram("span/repro.other").record(1e6)
    assert reader.read(_obs()) == 30.0
    assert "count=5" in reader.describe(_obs())


@pytest.mark.parametrize("reader,span", SPAN_READERS)
def test_span_reader_reads_none_without_its_span(reader, span):
    obs.REGISTRY.histogram("span/repro.other").record(1.0)
    assert reader.read(_obs()) is None
    assert reader.describe(_obs()) == "no spans"
    # reading creates no histogram
    assert set(obs.REGISTRY.histograms()) == {"span/repro.other"}


def test_span_readers_read_what_the_program_records():
    obs.enable()
    for _, span in SPAN_READERS:
        with obs.span(span):
            pass
    for reader, _ in SPAN_READERS:
        assert reader.read(_obs()) >= 0.0


def test_d2h_bytes_per_frame():
    eng = {"completed": 6, "waves": 2, "d2h_bytes": 8 * 2_073_600,
           "h2d_bytes": 6 * 2_073_600}
    got = serve_d2h_bytes_per_frame.read(_obs(eng))
    assert got == pytest.approx(8 / 6 * 2_073_600)
    assert "d2h_bytes=16588800" in serve_d2h_bytes_per_frame.describe(
        _obs(eng))


@pytest.mark.parametrize("engine", [None, {}, {"completed": 0, "waves": 0,
                                               "d2h_bytes": 0},
                                    {"completed": 3, "waves": 1}])
def test_d2h_bytes_per_frame_reads_none_without_the_counter(engine):
    assert serve_d2h_bytes_per_frame.read(_obs(engine)) is None
