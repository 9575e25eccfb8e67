"""steps_in_window.test: steps the window ran (a metric added as a file)."""


def read(rec):
    return float(rec["result"]["steps"])
