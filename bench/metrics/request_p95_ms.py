"""``request_p95_ms``: the 95th percentile of the wall time around
``AlertServer.serve_one`` (the controller's pick, the level's generate,
the feedback) over every request of the window."""

import numpy as np


def read(data):
    """95th percentile, in ms, of every request's wall time."""
    lat = data.get("latencies_s")
    if not lat:
        return None
    return 1e3 * float(np.percentile(np.asarray(lat), 95))
