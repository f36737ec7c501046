"""``serve_round_p95_ms``: the 95th percentile of the host gateway's
``serve_round`` spans (paging, the round's select, delivery, feedback),
from the program's flight recorder in the traced run."""

import numpy as np


def read(data):
    """95th percentile, in ms, of every round's span."""
    spans = data.get("serve_round_s")
    if not spans:
        return None
    return 1e3 * float(np.percentile(np.asarray(spans), 95))
