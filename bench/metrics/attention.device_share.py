"""attention.device_share: percent of the traced window's busy device time
spent under the program's ``attention`` scope (scores, mask, softmax and
P @ V with their backward; not the q/k/v/o projections, which are dense
units).  Device trace, ops attributed by their HLO op_name path
(``bench/lib/scopes.py``)."""
from bench.lib import scopes


def read(rec):
    return scopes.busy_share(rec, "attention")
