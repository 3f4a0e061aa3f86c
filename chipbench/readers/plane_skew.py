"""How far the chips' shares of one window's program lie apart, %: (the
slowest plane's ``program_ms`` − the fastest's) over the slowest's. Every
chip is given the same number of padded rows, so what is left is the
work that differs between shards. Nothing on fewer than two planes."""

from chipbench.readers.program_time_slowest import per_plane_ms


def read(run):
    times = per_plane_ms(run.planes)
    if len(times) < 2:
        return None
    return 100.0 * (max(times) - min(times)) / max(times)
