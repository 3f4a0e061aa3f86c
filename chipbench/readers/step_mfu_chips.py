"""``step_mfu`` for a run over several chips: one window's FLOPs times the
windows published in the measured window, over its seconds times the peak
of ALL the chips the run held."""


def read(run):
    n = len(run.windows_in)
    chips = run.launch.get("count")
    if not n or not chips or run.peak is None:
        return None
    return 100.0 * run.work[0] * n / (
        run.drive.seconds * chips * run.peak["flops_per_s"])
