"""90th percentile of how late the open-loop generator submitted a query
(submission minus due time), in milliseconds."""

UNIT = "ms"


def read(record, trace):
    if record["kind"] != "serve":
        return None
    return record["late_ms_p90"]
