"""Cells the window's sweeps computed, masked cells included (the program's
`mp.tile_cells.*` counters), per second of device busy time, in billions.
Over `sweep_gcells_s.join` (needed cells only) it gives the share of the
sweep's work spent on masked cells."""

UNIT = "Gcells/s"


def read(record, trace):
    cells = record.get("tile_cells", 0)
    if (record["kind"] != "join" or cells <= 0 or trace is None
            or trace.busy_s <= 0):
        return None
    return cells / trace.busy_s / 1e9
