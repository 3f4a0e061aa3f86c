"""Median over the measured window's windows of the time one window spent
in ``legs`` of the window path, ms — from the window's own record
(``/debug/window`` ``records``), joined to the window by its stamp. The
same windows and the same statistic as ``gauge_median``, so a leg stands
beside the gauge it is part of (``batch_ms`` + ``history_ms`` against
``assembly_ms``). With ``cpu_gap`` wall − CPU over the assembly legs
instead: the time the loop thread was kept off the processor (GIL, locks)
while it assembled. Nothing where the program serves no records, where one
of the measured windows has none (the body keeps the last 256), or where
none has these legs."""

from statistics import median

from chipbench.records import joined, leg_marks


def read(run, legs: list | None = None, cpu_gap: bool = False):
    records = joined(run)
    table = leg_marks(run.drive.debug.get("last"))
    if not records or any(g not in table for g in legs or ()):
        return None
    pairs = [("begin", "assembled")] if cpu_gap else [table[g] for g in legs]
    samples = []
    for rec in records:
        if any(rec.get(m) is None for ab in pairs for m in ab):
            continue
        took = sum(rec[b] - rec[a] for a, b in pairs)
        if cpu_gap:
            if rec.get("assembly_cpu_s") is None:
                continue
            took -= rec["assembly_cpu_s"]
        samples.append(took * 1e3)
    return median(samples) if samples else None
