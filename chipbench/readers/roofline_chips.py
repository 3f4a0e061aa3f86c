"""``roofline`` for a program over several chips: the least time ALL the
chips the run held could take for one window's work (``work.window_work``
over the chip's peaks times the device count) over the program's device
time on the slowest of them. ``roofline`` divides the whole fleet's work
by one chip's peak and reads the first plane only: right on one chip, four
times too high on four."""

from chipbench.readers.program_time_slowest import per_plane_ms
from chipbench.work import least_seconds


def read(run):
    times = per_plane_ms(run.planes)
    chips = run.launch.get("count")
    if not times or not chips or run.peak is None:
        return None
    peak = {k: v * chips for k, v in run.peak.items()}
    least, _bound = least_seconds(*run.work, peak)
    return 100.0 * least / (max(times) / 1e3)
