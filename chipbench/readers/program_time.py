"""Device time of one window's program, ms: the sum of the device-op
durations of the traced window over the program runs in it."""


def read(run):
    return run.program_ms
