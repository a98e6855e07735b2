"""Median time from a write's due time to its acknowledgement, ms, over
the window's acknowledged writes (each upsert and each delete; an
update's delete is due with its upsert and sent once that is
acknowledged), from the load generator's record (``w_*``)."""

import numpy as np

import stats


def read(rec):
    cl = rec["client"]
    if "w_status" not in cl:
        return None
    ok = (cl["w_status"] == stats.OK) & np.isfinite(cl["w_due"])
    if not ok.any():
        return None
    return float(np.median(cl["w_ack"][ok] - cl["w_due"][ok]) * 1e3)
