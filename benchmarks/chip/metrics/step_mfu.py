"""step_mfu: model FLOPs per image (counted from the configuration,
work.flops_per_image) times the images completed per second in the
window (as images_per_s counts them), over the chip's bf16 peak, in
percent."""
from benchmarks.chip import work


def read(run):
    rate = run.images_per_s()
    if not rate:
        return None
    peak = work.peak_for(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * work.flops_per_image(run.cell.model) * rate / peak
