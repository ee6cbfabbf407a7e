"""The reader of the program's gains counters, on a hand-seeded registry:
the share of uploads among the calls that counted, and None where the
program counted nothing (as a program without the counters does)."""
import pytest

from bench import generator, workload
from bench.metrics import gain_upload_pct
from repro import obs


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.disable()
    obs.REGISTRY.reset()
    yield
    obs.REGISTRY.reset()


def _obs():
    return generator.Observation(window_s=1.0, pixels_done=0.0, attempted=0,
                                 failed=0)


def test_reads_none_without_the_counters():
    obs.REGISTRY.counter("pipeline.calls").inc(5)
    assert gain_upload_pct.read(_obs()) is None
    assert gain_upload_pct.describe(_obs()) == "no counters"
    # reading creates no counter
    assert set(obs.REGISTRY.counters()) == {"pipeline.calls"}


@pytest.mark.parametrize("hits,uploads,want", [
    (1985, 15, 0.75), (0, 4, 100.0), (7, 0, 0.0)])
def test_reads_the_share_of_uploads(hits, uploads, want):
    if hits:
        obs.REGISTRY.counter("pipeline.gain_hits").inc(hits)
    if uploads:
        obs.REGISTRY.counter("pipeline.gain_uploads").inc(uploads)
    assert gain_upload_pct.read(_obs()) == pytest.approx(want)
    assert gain_upload_pct.describe(_obs()) == (
        f"gain_hits={hits} gain_uploads={uploads}")


def test_reads_what_the_program_counts():
    import jax.numpy as jnp
    import numpy as np

    from repro.core.pipeline import CompiledFilter, Filter2D
    from repro.core.requant import RequantSpec
    rq = RequantSpec(1, 0, rounding="nearest", dtype="uint8")
    x = jnp.zeros((16, 24), jnp.uint8)
    cf = CompiledFilter(Filter2D(window=3, dtype="uint8", requant=rq),
                        (16, 24), "core")
    k = np.ones((3, 3), np.int32)
    cf(x, k, rq)                         # not recording: not counted
    obs.enable()
    for m in (1, 2, 2, 1):
        cf(x, k, RequantSpec(m, 0, rounding="nearest", dtype="uint8"))
    assert gain_upload_pct.read(_obs()) == pytest.approx(25.0)


def test_the_metric_is_declared_for_the_paper_stream():
    (m,) = [m for m in workload.benchmark()["per_layer"]
            if m["name"] == "gain_upload_pct.stream"]
    assert m["source"] == "program_counter" and m["moves"] == "mpix_s"
    assert m["layer"] == "front door / planner"
    assert m["workloads"] == ["paper_u8_1080p.stream"]
