"""``device_idle_share.fleet``: the share of the traced window in which no
operation ran on the chip, in percent (fleet cells)."""

from bench import xtrace


def read(data):
    """Idle share of the reduced profiler trace."""
    return xtrace.idle_percent(data.get("trace"))
