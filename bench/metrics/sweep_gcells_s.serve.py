"""Answered queries' cells (`work.py`) per second of device busy time, in
billions."""

UNIT = "Gcells/s"


def read(record, trace):
    if (record["kind"] != "serve" or trace is None or trace.busy_s <= 0
            or not record["answered"]):
        return None
    return record["cells_per_query"] * record["answered"] / trace.busy_s / 1e9
