"""99th percentile of how late the load generator sent the requests due
in the traced part: send minus due time, ms. A starved generator shows
here, not as a fast server."""

import numpy as np

import layers
import stats


def read(rec):
    late = stats.lateness_ms(rec["client"])[layers.due_inside(rec)]
    late = late[np.isfinite(late)]
    return float(np.percentile(late, 99)) if len(late) else None
