"""Share of the device's idle time that falls inside ``legs`` of the window
path (their span names), %: the window records and the device trace on one
clock by the trace's own start time (``chipbench/records.py``), then
interval against interval. Nothing without records, without a trace,
without that start time, or where the records contradict it."""

from chipbench.records import idle_by_leg


def read(run, legs: list):
    body = run.drive.debug.get("last")
    if not run.planes:
        return None
    found = idle_by_leg(body, run.planes, run.launch, {"legs": legs},
                        run.program)
    if found is None or found["idle_s"] <= 0:
        return None
    return 100.0 * found["in_s"]["legs"] / found["idle_s"]
