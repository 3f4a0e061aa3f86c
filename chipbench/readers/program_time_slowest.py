"""Device time of one window's program on the chip that took longest, ms:
``trace.program_ms`` of every device plane by itself, and of those the
largest. A window is done when its slowest shard is, so this, and not the
first plane's time, is what a program over several chips costs a window.
Nothing where no plane ran the program."""

from chipbench.trace import program_ms


def per_plane_ms(planes: list) -> list[float]:
    """``program_ms`` of each plane that has whole runs of the program."""
    times = [program_ms([plane]) for plane in planes]
    return [t for t in times if t]


def read(run):
    times = per_plane_ms(run.planes)
    return max(times) if times else None
