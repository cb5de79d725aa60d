"""Host seconds of the z-stats prep of one join's own arguments, timed after
the window."""

UNIT = "s"


def read(record, trace):
    if record["kind"] != "join":
        return None
    return record.get("stats_prep_s")
