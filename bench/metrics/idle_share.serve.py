"""Share of the traced service window in which no operation ran on the
device."""

UNIT = "share"


def read(record, trace):
    if record["kind"] != "serve" or trace is None or trace.window_s <= 0:
        return None
    return 1.0 - trace.busy_s / trace.window_s
