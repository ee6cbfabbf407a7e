from repro.core.requant import RequantSpec
from repro.kernels.filter2d.halo import (DEFAULT_VMEM_BUDGET, HaloPlan,
                                         derive_strip_tile,
                                         hbm_bytes_per_pixel,
                                         hbm_write_bytes_per_pixel,
                                         make_plan, plan_vmem_working_set,
                                         read_amplification,
                                         read_bytes_per_pixel,
                                         stream_vmem_working_set)
from repro.kernels.filter2d.contract import KernelContract
from repro.kernels.filter2d.kernel import (acc_dtype, kernel_contract,
                                           out_dtype)
from repro.kernels.filter2d.ops import filter2d_pallas, filter_bank_pallas
from repro.kernels.filter2d.ref import filter2d_ref
