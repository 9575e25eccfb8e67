"""device_idle.train: percent of the traced training window in which no
operation ran on the device (1 - union of operation intervals / window)."""
from bench.lib import trace_reader


def read(rec):
    if rec.get("trace") is None:
        return None
    return trace_reader.idle_share(rec["trace"], rec["trace_window"])
