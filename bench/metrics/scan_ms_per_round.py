"""``scan_ms_per_round``: the megatick round clock's wall time (its
``megatick_scan`` phase timer: the chunked device scan and the result
scatter) per round served."""


def read(data):
    """Round-clock milliseconds per round."""
    if "scan_s" not in data or not data.get("rounds"):
        return None
    return 1e3 * data["scan_s"] / data["rounds"]
