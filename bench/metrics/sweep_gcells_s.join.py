"""Joins' cells (`work.py`) per second of device busy time, in billions."""

UNIT = "Gcells/s"


def read(record, trace):
    if record["kind"] != "join" or trace is None or trace.busy_s <= 0:
        return None
    return record["cells_per_join"] * record["joins"] / trace.busy_s / 1e9
