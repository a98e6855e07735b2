"""Wall time of one upsert in the server, ms: the program's
``live:upsert`` span (the passage's encoding on the device and its
append to the delta), as ``PipelineStats`` records it, over the whole
window, from its start to its close: the traced part holds too few
updates to read."""


def read(rec):
    a, b = (rec["stages"][x]["stages"].get("live_upsert")
            for x in ("start", "close"))
    if b is None:
        return None
    a = a or {"wall_s": 0.0, "dispatches": 0}
    n = b["dispatches"] - a["dispatches"]
    return (b["wall_s"] - a["wall_s"]) / n * 1e3 if n > 0 else None
