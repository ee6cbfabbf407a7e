"""Public-surface guard: the exported API is pinned by snapshot.

The point of the Filter2D/CompiledFilter redesign is ONE front door over
all executors; this test keeps future PRs from silently forking the API
again (a new public entry point must change this snapshot — a reviewed,
deliberate act — and every exported name must actually resolve).
"""
import repro
import repro.core as core

REPRO_ALL = [
    "BorderSpec",
    "CompiledFilter",
    "Filter2D",
    "RequantSpec",
    "obs",
    "serving",
]

CORE_ALL = [
    "ALIASES",
    "BorderSpec",
    "CoefficientFile",
    "CompiledFilter",
    "DEFAULT_VMEM_BUDGET",
    "EXECUTIONS",
    "FORMS",
    "Filter2D",
    "POLICIES",
    "RequantSpec",
    "SAME_SIZE_POLICIES",
    "decompose_separable",
    "default_bank",
    "filter2d",
    "filter2d_xla",
    "filter_bank",
    "macs_per_pixel",
    "np_pad_mode",
    "out_shape",
    "preset",
    "quantize_constant",
    "reduction_depth",
    "requantize_ref",
    "strip_height_for_vmem",
]


def test_repro_all_snapshot():
    assert sorted(repro.__all__) == REPRO_ALL
    for name in repro.__all__:
        assert getattr(repro, name) is not None


def test_core_all_snapshot():
    assert sorted(core.__all__) == CORE_ALL
    for name in core.__all__:
        assert getattr(core, name) is not None


def test_front_door_identity():
    """repro.Filter2D IS core.pipeline.Filter2D — one class, one cache."""
    from repro.core.pipeline import CompiledFilter, Filter2D
    assert repro.Filter2D is Filter2D is core.Filter2D
    assert repro.CompiledFilter is CompiledFilter is core.CompiledFilter
    assert repro.BorderSpec is core.BorderSpec
    assert repro.RequantSpec is core.RequantSpec


def test_executions_vocabulary():
    assert core.EXECUTIONS == ("auto", "core", "xla", "pallas",
                               "streaming", "sharded")
