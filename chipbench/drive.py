"""Drive one cell: set-up, the measured window, the close.

What the window does is all in the traffic file: ``loop`` (``open``: round
k is due at t0 + k*I and is posted then, whatever the aggregator is doing;
``closed``: post a round, wait for the first published window whose stamp
is later than the end of the POST, post the next) and the churn. Two
threads load the aggregator: the poster (this thread) and the poller, which
reads one node's published window every few milliseconds as a dashboard
would, notes each new window's first sight, and on each takes the
aggregator's own gauges and a few seeded nodes' answers for the check.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from chipbench.child import AggregatorChild, BenchFailure
from chipbench.fleetgen import Fleet

GAUGES = ("last_assembly_ms", "last_dispatch_ms", "last_wait_ms",
          "last_fetch_ms", "last_scatter_ms")
SAMPLE_NODES = 6  # answers kept from every window, half of them model nodes
# (the default: a configuration's ``check.sampled_nodes`` says otherwise)
WAIT_S = 60.0  # how long an answer may take before it counts as missing
THROTTLE_WAIT_S = (0.05, 5.0)  # a 429's retry_after is kept within these
THROTTLE_GIVE_UP_S = 30.0  # a batch throttled for longer fails the run


@dataclass
class Round:
    r: int  # the fleet's round (fill rounds come first)
    due: float
    batches: list = field(default_factory=list)  # [(nodes, start, end)]
    acked: int = 0
    keyframes: int = 0
    throttled: int = 0  # reports answered 429, each time one was
    throttle_wait_s: float = 0.0

    @property
    def start(self) -> float:
        return self.batches[0][1]

    @property
    def end(self) -> float:
        return self.batches[-1][2]


@dataclass
class Window:
    stamp: float  # the aggregator's clock at the window's snapshot
    seen: float  # first sight of it on /v1/results
    gauges: dict
    answers: dict  # node index -> its /v1/results payload, this window's


class Poller(threading.Thread):
    """First sight of every published window, from the client's side."""

    def __init__(self, child: AggregatorChild, fleet: Fleet,
                 sample: list[int], every_s: float) -> None:
        super().__init__(daemon=True, name="chipbench-poller")
        self.child, self.fleet, self.sample = child, fleet, sample
        self.every_s = every_s
        self.windows: list[Window] = []
        self.error: Exception | None = None
        self._stop_flag = threading.Event()
        self._cond = threading.Condition()
        self._probe = f"/v1/results?node={fleet.names[sample[0]]}"

    def run(self) -> None:
        last = 0.0
        try:
            while not self._stop_flag.is_set():
                status, body = self.child.request("GET", self._probe)
                now = time.time()
                if status == 200:
                    first = json.loads(body)
                    stamp = float(first["timestamp"])
                    if stamp > last:
                        last = stamp
                        self._new_window(stamp, now, first)
                self._stop_flag.wait(self.every_s)
        except Exception as err:  # surfaced by whoever waits on us
            self.error = err
            with self._cond:
                self._cond.notify_all()

    def _new_window(self, stamp: float, seen: float, first: dict) -> None:
        stats = self.child.get_json("/debug/window")["stats"]
        answers = {self.sample[0]: first}
        for i in self.sample[1:]:
            status, body = self.child.request(
                "GET", f"/v1/results?node={self.fleet.names[i]}")
            if status == 200:
                got = json.loads(body)
                if float(got["timestamp"]) == stamp:
                    answers[i] = got
        with self._cond:
            self.windows.append(Window(
                stamp, seen, {k: float(stats[k]) for k in GAUGES}, answers))
            self._cond.notify_all()

    def wait_stamp_after(self, after: float, timeout: float) -> Window:
        """The first window seen whose stamp is later than ``after``."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                if self.error is not None:
                    raise BenchFailure(f"poller: {self.error!r}")
                for win in self.windows:
                    if win.stamp > after:
                        return win
                left = deadline - time.monotonic()
                if left <= 0:
                    raise BenchFailure(
                        f"no window later than the POST within {timeout:.0f}s")
                self._cond.wait(min(left, 1.0))
                self.child.alive()

    def stop(self) -> None:
        self._stop_flag.set()
        if self.ident is not None:  # it was started
            self.join(timeout=30)


def _seconds(raw) -> float:
    """A ``retry_after`` as the aggregator sent it → seconds, 0 where it
    is no number (the clamp then gives the least wait)."""
    try:
        return float(raw)
    except (TypeError, ValueError):
        return 0.0


def _json_object(body: bytes) -> dict:
    try:
        got = json.loads(body)
    except ValueError:
        return {}
    return got if isinstance(got, dict) else {}


def post_round(child: AggregatorChild, fleet: Fleet, rnd: Round,
               bodies: list, state, clock=time) -> None:
    """POST the round's batches and answer as an agent would: a 409
    needs-keyframe with the keyframe, a 429 (of one row, or of the whole
    POST) by waiting out its ``retry_after`` and sending the same record
    again. A throttle is a stall on the clock, not a failed operation: the
    batch ends with its last resend. Anything else but 204 fails the run,
    and so does a batch still throttled after ``THROTTLE_GIVE_UP_S``: the
    traffic is chosen so that no operation fails. ``clock`` is for the
    tests: ``time()`` and ``sleep()``."""
    from kepler_tpu.fleet.wire import decode_report_batch, encode_report_batch

    for nodes, body in bodies:
        t0 = clock.time()
        pending, payload = nodes, body
        asked, throttled_at = 0, None
        while pending:
            status, resp = child.request("POST", "/v1/reports", payload)
            if status == 429:  # admission shed the POST whole
                rows = [{"status": 429, **_json_object(resp)}] * len(pending)
            elif status == 200:
                rows = json.loads(resp)["results"]
            else:
                raise BenchFailure(f"POST /v1/reports -> {status} "
                                   f"{resp[:120]!r}")
            keyframes, again, hint = [], [], 0.0
            for k, (i, row) in enumerate(zip(pending, rows)):
                if row["status"] == 204:
                    rnd.acked += 1
                elif row["status"] == 409 and row.get("needs_keyframe"):
                    keyframes.append(i)
                elif row["status"] == 429:
                    again.append(k)
                    hint = max(hint, _seconds(row.get("retry_after")))
                else:
                    raise BenchFailure(
                        f"report of {fleet.names[i]} -> {row}")
            if not (keyframes or again):
                break
            records = []
            if again:
                now = clock.time()
                throttled_at = now if throttled_at is None else throttled_at
                if now - throttled_at > THROTTLE_GIVE_UP_S:
                    raise BenchFailure(
                        f"{len(again)} report(s) still throttled (429) "
                        f"after {now - throttled_at:.0f}s")
                wait = min(max(hint, THROTTLE_WAIT_S[0]), THROTTLE_WAIT_S[1])
                rnd.throttled += len(again)
                rnd.throttle_wait_s += wait
                clock.sleep(wait)
                sent = decode_report_batch(payload)
                records = [sent[k] for k in again]
            if keyframes:
                asked += 1
                if asked >= 3:
                    raise BenchFailure(
                        "the aggregator kept asking for keyframes")
                rnd.keyframes += len(keyframes)
                records += decode_report_batch(fleet.keyframe_batch(
                    keyframes, state, clock.time()))
            pending = [pending[k] for k in again] + keyframes
            payload = encode_report_batch(records)
        rnd.batches.append((nodes, t0, clock.time()))


@dataclass
class Drive:
    """Everything one run observed, for the metrics and the check."""

    fleet: Fleet
    seconds: float
    t_open: float = 0.0
    t_close: float = 0.0
    setup_s: float = 0.0
    rounds: list = field(default_factory=list)  # measured rounds only
    all_rounds: list = field(default_factory=list)  # fill and warm-up too
    windows: list = field(default_factory=list)
    final: dict = field(default_factory=dict)  # /v1/results after the close
    final_round: int = -1
    debug: dict = field(default_factory=dict)
    trace_marks: dict = field(default_factory=dict)
    final_error: str = ""
    setup_parts: dict = field(default_factory=dict)  # seconds since start
    published_open: int = 0  # the aggregator's count of published windows
    published_close: int = 0
    count_from: float = 0.0  # when each reading of that count was taken
    count_to: float = 0.0


def published_total(child: AggregatorChild) -> int:
    """``kepler_fleet_attributions_total`` from ``/metrics``."""
    status, body = child.request("GET", "/metrics")
    if status == 200:
        for line in body.decode(errors="replace").splitlines():
            if line.startswith("kepler_fleet_attributions_total"):
                return int(float(line.rsplit(" ", 1)[1]))
    raise BenchFailure("no kepler_fleet_attributions_total on /metrics")


def sample_nodes(fleet: Fleet) -> list[int]:
    count = int(fleet.config.get("check", {}).get("sampled_nodes",
                                                  SAMPLE_NODES))
    rng = np.random.default_rng([fleet.seed, 2])
    model = np.flatnonzero(fleet.mode == 1)
    ratio = np.flatnonzero(fleet.mode == 0)
    half = count // 2
    picks = list(rng.choice(model, min(half, len(model)), replace=False))
    picks += list(rng.choice(ratio, min(count - half, len(ratio)),
                             replace=False))
    return [int(i) for i in picks]


class Poster:
    """Posts the fleet's rounds in order. The next round is encoded right
    after a post, between two posts and not at its due time, so the
    generator's own work stays off the clock."""

    def __init__(self, child: AggregatorChild, fleet: Fleet,
                 out: Drive) -> None:
        self.child, self.fleet, self.out = child, fleet, out
        self.r = 0
        self._prepare()

    def _prepare(self) -> None:
        self.state = self.fleet.state(self.r)
        self.bodies = self.fleet.batches(self.state, time.time())

    def post(self, due: float, measured: bool = False) -> Round:
        rnd = Round(r=self.r, due=due)
        post_round(self.child, self.fleet, rnd, self.bodies, self.state)
        self.out.all_rounds.append(rnd)
        if measured:
            self.out.rounds.append(rnd)
        self.r += 1
        self._prepare()
        return rnd


def run_window(child: AggregatorChild, fleet: Fleet, traffic: dict,
               history: int, seconds: float, traced: bool,
               t_start: float) -> Drive:
    """Fill the history, warm up, measure for ``seconds``, close."""
    out = Drive(fleet=fleet, seconds=seconds)
    interval = float(traffic["interval_s"])
    poller = Poller(child, fleet, sample_nodes(fleet),
                    float(traffic.get("poll_every_s", 0.005)))
    poster = Poster(child, fleet, out)

    # history is full before the window opens: ``history`` rounds as fast
    # as ingest takes them, then warm-up rounds in closed loop until the
    # pipeline has published windows of the full fleet
    out.setup_parts["ready_s"] = time.time() - t_start
    phase = "fill"
    try:
        for _ in range(history):
            poster.post(time.time())
        out.setup_parts["fill_s"] = time.time() - t_start
        poller.start()
        phase = "warmup"
        for _ in range(int(traffic.get("warmup_rounds", 3))):
            rnd = poster.post(time.time())
            poller.wait_stamp_after(rnd.end, WAIT_S + 20 * interval)
        out.debug = child.get_json("/debug/window")
        if traced:
            out.trace_marks["start"] = child.trace("start")

        phase = "window"
        out.count_from = time.time()
        out.published_open = published_total(child)
        t0 = time.time() + 0.05
        out.t_open, out.setup_s = t0, t0 - t_start
        t1 = t0 + seconds
        if traffic["loop"] == "open":
            k = 0
            while t0 + k * interval < t1:
                due = t0 + k * interval
                wait = due - time.time()
                if wait > 0:
                    time.sleep(wait)
                poster.post(due, measured=True)
                k += 1
                child.alive()
            wait = t1 - time.time()
            if wait > 0:
                time.sleep(wait)
        elif traffic["loop"] == "closed":
            while time.time() < t1:
                rnd = poster.post(time.time(), measured=True)
                poller.wait_stamp_after(rnd.end, WAIT_S)
        else:
            raise BenchFailure(f"traffic loop {traffic['loop']!r}: "
                               "open or closed")
        phase = "close"
        out.t_close = t1
        out.published_close = published_total(child)
        time.sleep(0.05)  # the poller sees what was published before that
        out.count_to = time.time()
        if traced:
            out.trace_marks["stop"] = child.trace("stop")
        # the close: every round posted gets its window, late or not;
        # then the whole fleet's answers, which the pipeline published
        # from the state after the last POST, for the check
        last = out.all_rounds[-1]
        try:
            poller.wait_stamp_after(last.end, WAIT_S)
            status, body = child.request("GET", "/v1/results", timeout=300)
            if status != 200:
                raise BenchFailure(f"GET /v1/results -> {status}")
            out.final = json.loads(body)
            out.final_round = last.r
        except BenchFailure as err:
            out.final_error = str(err)  # the check counts it
        out.debug = {"first": out.debug,
                     "last": child.get_json("/debug/window")}
        poller.stop()
        if poller.error is not None:
            raise BenchFailure(f"poller: {poller.error!r}")
    except BenchFailure as err:
        err.phase = err.phase or phase  # the FAIL line names it
        raise
    except (OSError, http.client.HTTPException) as err:
        # a request that timed out or was cut: a failed run, by its name
        fail = BenchFailure(f"{type(err).__name__}: {err}")
        fail.phase = phase
        raise fail from err
    finally:
        poller.stop()
    out.windows = poller.windows  # warm-up's too: the readers cut by time
    return out
