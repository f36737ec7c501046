"""``mfu.serve``: model FLOPs the window's requests needed at their picked
levels (``bench.lm_ref.request_flops``), per second of the window, as a
share of the chip's bf16 peak, in percent."""


def read(data):
    """100 x FLOPs / window seconds / peak."""
    peaks = data.get("peaks")
    if not peaks or not data.get("model_flops") or data["elapsed_s"] <= 0:
        return None
    return 100.0 * data["model_flops"] / data["elapsed_s"] / \
        peaks["bf16_flops"]
