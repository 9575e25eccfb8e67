"""head_loss.device_share: percent of the traced window's busy device time
spent under the program's ``head_loss`` scope (final norm, logits over the
vocabulary, cross-entropy and their backward).  Device trace, ops
attributed by their HLO op_name path (``bench/lib/scopes.py``)."""
from bench.lib import scopes


def read(rec):
    return scopes.busy_share(rec, "head_loss")
