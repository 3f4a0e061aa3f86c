"""``chipbench/launch.py`` with the timed path broken on purpose.

Only tests start this. ``CHIPBENCH_TEST_FAULT`` names the fault planted
under the aggregator before it starts:

- ``answer_altered``: one pod's estimate is raised by a tenth where the
  estimator produces it;
- ``history_stalled``: after the fill the per-pod history stops advancing
  (the state is returned unchanged), so windows are computed from stale
  ticks;
- ``half_left_out``: every second model node's history is never read, so
  half of the model rows are estimated from nothing.

The comparison has to call each run not correct. One more stalls the path
and breaks nothing:

- ``shed_once``: admission turns one report away (429, ``retry_after``
  0.2 s), and with it the rest of its POST, as it does when the machine
  freezes; the harness has to wait, send them again, and end ``correct``.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def plant(fault: str) -> None:
    from kepler_tpu.fleet import aggregator as agg
    from kepler_tpu.models import temporal

    if fault == "answer_altered":
        real = temporal.predict_temporal

        def altered(*args, **kw):
            watts = real(*args, **kw)
            return watts.at[1, 0].multiply(1.1)

        # the fleet program looks the estimator up when it is traced
        temporal.predict_temporal = altered
    elif fault == "history_stalled":
        real_push = agg.Aggregator._push_history
        after = int(os.environ["CHIPBENCH_TEST_FAULT_AFTER"])
        calls = [0]

        def stalled(self, report):
            calls[0] += 1
            if calls[0] <= after:
                real_push(self, report)

        agg.Aggregator._push_history = stalled
    elif fault == "half_left_out":
        real_windows = agg.Aggregator._history_windows

        def halved(self, batch):
            hist, tv = real_windows(self, batch)
            hist[3::4] = 0.0
            tv[3::4] = False
            return hist, tv

        agg.Aggregator._history_windows = halved
    elif fault == "shed_once":
        from kepler_tpu.fleet import admission

        real_admit = admission.AdmissionController.admit
        at = int(os.environ["CHIPBENCH_TEST_FAULT_AFTER"])
        calls = [0]

        def shed(self, priority):
            calls[0] += 1
            if calls[0] == at:
                return 0.2
            return real_admit(self, priority)

        admission.AdmissionController.admit = shed
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ["CHIPBENCH_TEST_FAULT"])
    from chipbench import launch

    sys.exit(launch.main())
