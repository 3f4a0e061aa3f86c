"""The seeded parameters on disk to the first measured instant: the
aggregator's start and its load of them, compiles, fill and warm-up."""


def read(run):
    return run.drive.setup_s
