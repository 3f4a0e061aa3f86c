"""Share of the device's idle time that falls inside ``legs`` of the window
path (their span names), %: the window records and the device trace on one
clock (``chipbench/records.py``), then interval against interval. Nothing
without records, without a trace, or where the alignment contradicts one
of its hard bounds."""

from chipbench.records import idle_by_leg


def read(run, legs: list):
    body = run.drive.debug.get("last")
    if not run.planes:
        return None
    found = idle_by_leg(body, run.planes, run.launch.get("marks", {}),
                        {"legs": legs})
    if found is None or found["idle_s"] <= 0:
        return None
    return 100.0 * found["in_s"]["legs"] / found["idle_s"]
