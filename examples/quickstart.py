"""Quickstart: the paper's general-purpose spatial filter in five minutes.

  PYTHONPATH=src python examples/quickstart.py

Covers: the runtime coefficient file, all four filter forms, border
policies, the streaming row-buffer executor, and the Pallas kernel path.
"""
import numpy as np
import jax.numpy as jnp

from repro.core import (BorderSpec, FORMS, Filter2D, default_bank, filter2d,
                        preset)
from repro.data import SyntheticFrames
from repro.kernels.filter2d import filter2d_pallas


def main():
    frame = jnp.asarray(SyntheticFrames(480, 640).frame_np(0)[..., 0])
    print(f"frame: {frame.shape} {frame.dtype}")

    # 1. runtime-programmable coefficients (paper §I): one compiled filter,
    #    many functions — write new coefficients, no recompilation.
    cf = default_bank(w_max=7)
    for slot, name in [(0, "gaussian"), (3, "sobel_x"), (6, "sharpen")]:
        y = filter2d(frame, cf.read(slot))
        print(f"slot {slot} ({name:8s}): out {y.shape}, "
              f"mean {float(y.mean()):+.4f}")

    # 2. the four reduction forms (paper §II) agree to float tolerance
    k = preset("gaussian", 7)
    ys = [filter2d(frame, k, form=f) for f in FORMS]
    for f, y in zip(FORMS[1:], ys[1:]):
        err = float(jnp.max(jnp.abs(y - ys[0])))
        print(f"form {f:10s}: max |Δ| vs direct = {err:.2e}")

    # 3. border policies (paper §III): same frame size out, no stall.
    #    Aliases (zero/replicate/reflect) normalise onto the paper's names.
    for pol in ("mirror", "duplicate", "constant", "wrap", "zero",
                "replicate", "reflect"):
        y = filter2d(frame, k, border=BorderSpec(pol))
        assert y.shape == frame.shape
    print("border policies keep the frame size (paper Table IV)")

    # 4. streaming row-buffer executor == frame-resident result
    y_res = filter2d(frame, k)
    y_str = Filter2D(window=7).compile(frame, "streaming", strip_h=96)(
        frame, k)
    print(f"streaming vs resident: max |Δ| = "
          f"{float(jnp.max(jnp.abs(y_str - y_res))):.2e}")

    # 5. the Pallas TPU kernel (interpret mode on CPU): the halo engine
    #    resolves every border policy in-kernel — wrap included — while
    #    streaming the raw frame read-once from HBM.
    y_pl = filter2d_pallas(frame, k, regime="stream", strip_h=128)
    print(f"pallas stream kernel:  max |Δ| = "
          f"{float(jnp.max(jnp.abs(y_pl - y_res))):.2e}")
    y_wr = filter2d_pallas(frame, k, border=BorderSpec("wrap"),
                           regime="stream", strip_h=128)
    y_wc = filter2d(frame, k, border=BorderSpec("wrap"))
    print(f"pallas in-kernel wrap: max |Δ| = "
          f"{float(jnp.max(jnp.abs(y_wr - y_wc))):.2e}")


if __name__ == "__main__":
    main()
