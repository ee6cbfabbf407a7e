"""The bugged kernel bodies behind ``analysis_fixtures``.

One parameterized copy of the shipped overlap-path kernel
(``repro.kernels.filter2d.kernel._halo_kernel``), with the seeded bug
selected by name. Everything else — scratch layout, bank arithmetic,
fill/store scheduling, the pallas_call specs — mirrors the shipped
kernel byte for byte, so the only verifier finding a fixture can produce
is the one its bug plants (pinned in ``tests/test_analysis.py``).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.border_spec import BorderSpec
from repro.core.filter2d import apply_requant
from repro.core.requant import RequantSpec
from repro.kernels.filter2d import halo
from repro.kernels.filter2d import kernel as K


def _bugged_kernel(x_ref, c_ref, *rest, plan, w, n_filters, grid_order,
                   ext_banks, out_banks, bug):
    """The shipped overlap-path grid step with ``bug`` planted."""
    if plan.requant is not None:
        q_ref, o_ref, ext_ref, obuf_ref, fill_sem, store_sem = rest
    else:
        q_ref = None
        o_ref, ext_ref, obuf_ref, fill_sem, store_sem = rest
    m = pl.program_id(0)
    j = pl.program_id(1)
    if grid_order == "filters_innermost":
        i, f = pl.program_id(2), pl.program_id(3)
        n_i = pl.num_programs(2)
        first_fill = (f == 0) if n_filters > 1 else None
        t = i * n_filters + f
    else:
        f, i = pl.program_id(2), pl.program_id(3)
        n_i = pl.num_programs(3)
        # BUG stale_guard: the guard hard-codes "fill at the first filter
        # step" against a grid whose innermost dim is the STRIP — filters
        # beyond the first read whatever strip the bank last held
        first_fill = (f == 0) if bug == "stale_guard" else None
        t = f * n_i + i
    T = plan.rows.n * n_filters
    S, Tw = plan.rows.block, plan.cols.block
    frame = x_ref.at[m]

    bank = jax.lax.rem(i, ext_banks)
    nxt = jax.lax.rem(i + 1, ext_banks)
    K._when(first_fill, i == 0)(
        lambda: halo.start_fill(frame, ext_ref.at[bank],
                                fill_sem.at[bank], i, j, plan))
    if ext_banks == 2:
        K._when(first_fill, i + 1 < n_i)(
            lambda: halo.start_fill(frame, ext_ref.at[nxt],
                                    fill_sem.at[nxt], i + 1, j, plan))
    K._when(first_fill)(
        lambda: halo.wait_fill(frame, ext_ref.at[bank],
                               fill_sem.at[bank], i, j, plan))

    adt = jnp.int32 if plan.requant is not None else o_ref.dtype
    win = ext_ref.at[bank][pl.ds(0, plan.rows.window),
                           pl.ds(0, plan.cols.window)]
    r0, c0 = plan.rows.shift, plan.cols.shift
    if bug == "widen_mac":
        # BUG: the narrow stream widens to FLOAT at the MAC input — the
        # fixed-point datapath allows the int32 accumulator only
        ext = win.astype(jnp.float32)
        y = K._reduce_taps(ext, c_ref[0].astype(jnp.float32), S, Tw, w,
                           "direct", r0, c0).astype(jnp.int32)
    else:
        ext = win.astype(adt)
        y = K._reduce_taps(ext, c_ref[0], S, Tw, w, "direct", r0, c0)
    if plan.requant is not None:
        y = apply_requant(y, q_ref[f, 0], q_ref[f, 1],
                          rounding=plan.requant.rounding,
                          out_dtype=o_ref.dtype)

    ob = jax.lax.rem(t, out_banks)
    dst = o_ref.at[m, f, pl.ds(i * S, S), pl.ds(j * Tw, Tw)]
    if bug == "premature_reuse":
        # BUG: the bank is rewritten FIRST; the store still flying out of
        # it (issued two steps ago) reads torn data
        obuf_ref[ob] = y
        if out_banks == 2:
            K._when(t >= 2)(
                lambda: pltpu.make_async_copy(obuf_ref.at[ob], dst,
                                              store_sem.at[ob]).wait())
    else:
        if out_banks == 2:
            K._when(t >= 2)(
                lambda: pltpu.make_async_copy(obuf_ref.at[ob], dst,
                                              store_sem.at[ob]).wait())
        obuf_ref[ob] = y
    pltpu.make_async_copy(obuf_ref.at[ob], dst, store_sem.at[ob]).start()

    last = (T - 1) % out_banks
    if out_banks == 2 and T >= 2:
        K._when(t == T - 1)(
            lambda: pltpu.make_async_copy(obuf_ref.at[(T - 2) % 2], dst,
                                          store_sem.at[(T - 2) % 2]).wait())
    K._when(t == T - 1)(
        lambda: pltpu.make_async_copy(obuf_ref.at[last], dst,
                                      store_sem.at[last]).wait())
    if bug == "unpaired_start":
        # BUG: one extra store is launched at the very last grid step and
        # never waited — it outlives the kernel without a drain
        K._when(m == pl.num_programs(0) - 1,
                j == pl.num_programs(1) - 1, t == T - 1)(
            lambda: pltpu.make_async_copy(obuf_ref.at[last], dst,
                                          store_sem.at[last]).start())


def _build_call(plan, bug, num_filters, grid_order, dtype):
    """The shipped overlap pallas_call wrapper around the bugged body."""
    w = 2 * plan.rows.r + 1
    S, Tw = plan.rows.block, plan.cols.block
    n_i, n_j = plan.rows.n, plan.cols.n
    N = num_filters
    ext_banks, out_banks = halo.plan_banks(plan, N, True)
    odt = K.out_dtype(plan, jnp.dtype(dtype))

    def kernel_fn(planes, coeffs, q=None):
        M = planes.shape[0]
        if grid_order == "filters_innermost":
            c_map = lambda m, jj, ii, f: (f, 0, 0)        # noqa: E731
            grid = (M, n_j, n_i, N)
        else:
            c_map = lambda m, jj, f, ii: (f, 0, 0)        # noqa: E731
            grid = (M, n_j, N, n_i)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((1, w, w), c_map)]
        operands = [planes, coeffs]
        if plan.requant is not None:
            operands.append(q)
            in_specs.append(
                pl.BlockSpec(memory_space=pltpu.SMEM))
        return pl.pallas_call(
            functools.partial(_bugged_kernel, plan=plan, w=w, n_filters=N,
                              grid_order=grid_order, ext_banks=ext_banks,
                              out_banks=out_banks, bug=bug),
            out_shape=jax.ShapeDtypeStruct((M, N, n_i * S, n_j * Tw), odt),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((ext_banks, plan.eh, plan.ew), planes.dtype),
                pltpu.VMEM((out_banks, S, Tw), odt),
                pltpu.SemaphoreType.DMA((ext_banks,)),
                pltpu.SemaphoreType.DMA((out_banks,))],
            interpret=False,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary")),
            name=f"filter2d_halo_fixture_{bug}",
        )(*operands)

    return kernel_fn


# name -> (the pass that must flag it, the finding-message substring that
# identifies the intended bug class, build parameters)
FIXTURES = {
    "stale_guard": dict(expect_pass="bank_hazard", expect_msg="stale",
                        num_filters=2, grid_order="strips_innermost",
                        dtype="float32"),
    "unpaired_start": dict(expect_pass="dma_pairing",
                           expect_msg="never waited",
                           num_filters=1, grid_order="filters_innermost",
                           dtype="float32"),
    "premature_reuse": dict(expect_pass="bank_hazard",
                            expect_msg="rewritten while its store",
                            num_filters=1, grid_order="filters_innermost",
                            dtype="float32"),
    "widen_mac": dict(expect_pass="width_lint", expect_msg="floating",
                      num_filters=1, grid_order="filters_innermost",
                      dtype="int8", requant=RequantSpec(1, 7, dtype="int8")),
}

H, W, WIN, STRIP, TILE = 24, 300, 5, 8, 128


def build(name: str):
    """(plan, verify_kernel kwargs) for the named fixture."""
    cfg = FIXTURES[name]
    plan = halo.make_plan(H, W, WIN, BorderSpec("mirror"), STRIP, TILE,
                          cfg["dtype"], requant=cfg.get("requant"))
    fn = _build_call(plan, name, cfg["num_filters"], cfg["grid_order"],
                     cfg["dtype"])
    return plan, dict(kernel_fn=fn, num_filters=cfg["num_filters"],
                      overlap=True, grid_order=cfg["grid_order"],
                      dtype=cfg["dtype"], key=f"fixture/{name}")
