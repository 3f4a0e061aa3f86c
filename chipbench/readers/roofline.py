"""The least time the chip could take for one window's work (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, both counted from the
cell's shapes by ``work.window_work``) over the program's device time."""

from chipbench.work import least_seconds


def read(run):
    if not run.program_ms or run.peak is None:
        return None
    least, _bound = least_seconds(*run.work, run.peak)
    return 100.0 * least / (run.program_ms / 1e3)
