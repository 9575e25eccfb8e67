"""dense_unit.glue_share: percent of the device time under the program's
``dense_unit`` scope that is not spent in its named int8 kernels
(``fxp_matmul``, ``bp_gstep``, ``sgd_dw_update``): the quantize, rescale
and relayout work around the MXU.  Device trace, ops attributed by their
HLO op_name path and kernels by name (``bench/lib/scopes.py``)."""
from bench.lib import scopes


def read(rec):
    trace = scopes.scoped_trace(rec)
    if trace is None:
        return None
    win = rec["trace_window"]
    unit = scopes.scope_s(trace, win, "dense_unit")
    if unit <= 0:
        return None
    kern = scopes.kernel_s(trace, win, scopes.INT8_KERNELS, "dense_unit")
    return 100.0 * (unit - kern) / unit
