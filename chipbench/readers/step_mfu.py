"""The whole window path's share of the chip's peak: one window's FLOPs
times the windows published in the measured window, over its seconds."""


def read(run):
    n = len(run.windows_in)
    if not n or run.peak is None:
        return None
    return 100.0 * run.work[0] * n / (
        run.drive.seconds * run.peak["flops_per_s"])
