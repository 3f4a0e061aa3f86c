"""Launcher start to the first measured instant."""


def read(run):
    return run.drive.setup_s
