"""dense_unit_roofline: the dense unit's least time over the device time of
its kernels, as a percent.

The least time sums, over every dense-unit grid of the window's steps
(x @ w, dz @ w^T and x^T @ dz of each layer's q, k, v, o, gate, up and
down), max(2MNK / int8 peak, (MK + KN + 4MN) bytes / HBM peak), from the
logical shapes, so it reads the same work whatever path implements it.
The device time is the summed duration of the int8 dense-unit kernels'
trace events.  Their Pallas calls carry no name of their own yet, so they
are found as the Mosaic custom calls (``tpu_custom_call``) whose operands
are int8: in a training step those are exactly fxp_matmul, bp_gstep and
sgd_dw_update (checked by hand on a v5e trace, where XLA's own int8
``ConcatBitcast`` custom calls also appear and are not counted).
"""
from bench.lib import flops, trace_reader

KERNELS = (r"= [^ ]+ custom-call\(s8\[.*custom_call_target=\"tpu_custom_call\"",)


def read(rec):
    trace, peaks = rec.get("trace"), rec.get("peaks")
    if trace is None or not peaks:
        return None
    t = rec["traffic"]
    k_s = trace_reader.kernel_s(trace, rec["trace_window"], KERNELS)
    if k_s <= 0:
        return None
    grids = rec["run"].fam.dense_unit_grids(rec["model"],
                                            t["batch"] * t["seq"])
    least = rec["result"]["steps"] * flops.least_time_s(
        grids, peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"])
    return 100.0 * least / k_s
