"""1 - (union of the device-op intervals over the traced window), %."""


from chipbench.trace import idle_share


def read(run):
    idle = idle_share(run.planes, run.window_s or 0.0)
    return None if idle is None else 100.0 * idle
