"""``hbm_share.hybrid_decode``: the least bytes the hybrid's decode steps
had to move, over the device time they took, as a share of the chip's
HBM bandwidth, in percent.

Both read the requests served under the profiler, each wholly inside
the trace.  Bytes (``data["decode_bytes"]``, from
``bench.hybrid_ref.decode_step_bytes``): per step every weight outside
the expert FFNs once (the embedding's batch rows only), the Mamba states
read and written and the KV prefix read at the step's cache length, and
per held expert a token reached (the request's ``moe_experts_hit``) its
three matrices.  Time (``data["decode_device_s"]``): the decode
program's runs on the device in the trace.  No implementation can move
fewer bytes, so the share cannot pass 100 % unless the time leaves out
work."""


def read(data):
    """100 x bytes / device seconds / HBM bytes per second."""
    peaks = data.get("peaks")
    if not peaks or not data.get("decode_device_s"):
        return None
    return 100.0 * data["decode_bytes"] / data["decode_device_s"] / \
        peaks["hbm_bytes_per_s"]
