"""The paper's contribution: high-throughput 2D spatial filtering, TPU-native.

Submodules:
  border_spec  — the policy-neutral BorderSpec + aliases (paper Table IV)
  borders      — border policies as lean index remaps (paper §III)
  filters      — runtime coefficient file + preset bank (paper §I/§II)
  filter2d     — direct/transposed/tree/compress forms (paper §II)
  requant      — the fused output-scaler spec + numpy reference (paper §IV)
  streaming    — row-strip streaming executor with carried row buffer,
                 and the band body both row-buffer executors run
  distributed  — shard_map halo exchange (the row buffer, distributed)
  pipeline     — the plan-and-execute front door: Filter2D → CompiledFilter

``Filter2D(...).compile(frame_spec)`` is the one front door over every
executor: ``compile(frame_spec, "streaming", strip_h=...)`` runs the strip
scan and ``compile(frame_spec, "sharded", mesh=...)`` the row-sharded
executor, which have no entry point of their own. The
export list below is pinned by a snapshot test (tests/test_public_api.py)
so the public surface cannot fork silently.
"""
from repro.core.border_spec import (ALIASES, BorderSpec, POLICIES,
                                    SAME_SIZE_POLICIES, np_pad_mode,
                                    out_shape, quantize_constant)
from repro.core.filter2d import (FORMS, filter2d, filter2d_xla, filter_bank,
                                 macs_per_pixel, reduction_depth)
from repro.core.filters import (CoefficientFile, decompose_separable,
                                default_bank, preset)
from repro.core.requant import RequantSpec, requantize_ref
from repro.core.streaming import strip_height_for_vmem
from repro.core.pipeline import (DEFAULT_VMEM_BUDGET, EXECUTIONS,
                                 CompiledFilter, Filter2D)

__all__ = [
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
