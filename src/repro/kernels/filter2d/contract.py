"""The kernel's *declared* dataflow contract: operand, scratch and grid
roles, stated by the kernel package itself.

The static verifier (``repro.analysis``) lowers a traced kernel into a
dataflow IR and checks it against invariants — but a jaxpr only carries
positional variables, not meanings. This module is where the kernel
publishes the meanings: which invar is the frame vs the coefficient file,
which scratch ref is the halo scratch vs the output buffer vs a DMA
semaphore, which grid axis is the plane/tile/strip/filter dim and how
many banks each scratch carries. The contract lives in the kernels
package (next to the code that makes it true) so the analysis subsystem
imports *us*, never the reverse — no import cycle, and a kernel change
that breaks the contract shows up as a verifier finding, not a silent
re-interpretation.

``KernelContract`` is pure data (hashable, serialisable via
``dataclasses.asdict``); :func:`kernel_contract` in ``kernel.py`` builds
one from the same (plan, num_filters, overlap, grid_order) knobs that
shape the kernel trace.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# Scratch role vocabulary (what the verifier's passes key on):
#   ext       — the halo-extended input scratch (banked when overlapped)
#   obuf      — the banked output tile buffer (overlap path only)
#   fill_sem  — DMA semaphore(s) for the halo fill copies
#   store_sem — DMA semaphore(s) for the async output stores
SCRATCH_ROLES = ("ext", "obuf", "fill_sem", "store_sem")

# Grid axis role vocabulary: plane and tile are parallel (megacore-
# partitionable); strip and filter are the arbitrary inner dims whose
# order is the ``grid_order`` knob.
AXIS_ROLES = ("plane", "tile", "strip", "filter")


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Declared dataflow roles of one ``filter2d_halo`` trace.

    ``operands``/``outputs``/``scratch`` name the pallas_call's kernel
    invars in positional order (inputs, then outputs, then scratch — the
    order Pallas binds them). ``axes`` names the grid dims in grid order.
    ``ext_banks``/``out_banks`` are the bank counts the kernel allocates
    (:func:`~repro.kernels.filter2d.halo.plan_banks`); ``serial_ref``
    marks the contract of the one-bank reference path whose fill schedule
    defines correct scratch contents for the banked kernel.
    """

    operands: Tuple[str, ...]         # ("frame", "coeffs"[, "qparams"])
    outputs: Tuple[str, ...]          # ("out",)
    scratch: Tuple[str, ...]          # roles from SCRATCH_ROLES, in order
    axes: Tuple[str, ...]             # roles from AXIS_ROLES, in grid order
    grid_order: str
    overlap: bool
    num_filters: int
    form: str
    ext_banks: int
    out_banks: int
    has_requant: bool

    def axis(self, role: str) -> Optional[int]:
        """Grid-dim index of ``role`` (``None`` when absent)."""
        try:
            return self.axes.index(role)
        except ValueError:
            return None

    def scratch_role(self, k: int) -> str:
        """Role of the k-th scratch operand."""
        return self.scratch[k]

    @property
    def serial_ref(self) -> bool:
        """True for the one-bank serial reference path."""
        return not self.overlap
