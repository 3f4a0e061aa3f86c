"""Per-workload feature-history ring buffer (the time axis' host side).

The reference keeps no history — each tick's deltas are consumed and
dropped (`internal/monitor/monitor.go:317-356` replaces the snapshot
wholesale). The temporal estimator (`kepler_tpu.models.temporal`) needs the
last T ticks of the feature vector per workload, so this buffer accretes
one row per workload per `push()` and materialises right-padded
``[W, T, F]`` windows on demand.

Storage: ONE float32 ``[capacity, T, F]`` slab per buffer, a ring of T
rows per slot, with integer ``cursor`` / ``count`` / ``seen`` vectors over
the slots; a workload id owns a slot for as long as it lives (a dict id →
slot, a list slot → id, a free list). Capacity doubles when the free list
runs dry and never shrinks. Slot 0 is never handed out: it stays empty,
and is what an unknown id reads.

Cost: `push()` and `window_arrays()` are a fixed handful of whole-array
NumPy operations over the batch's slot vector — no Python statement per
workload but the dict lookup that resolves an id to its slot, and that
only when the id list differs from the last push's (an unchanged list
reuses its slot vector). A push walks the slots for eviction only while
the buffer holds ids the batch did not name.

Host-side numpy only: rows are tiny (F=7 f32), the buffer is O(W×T)
bytes, and it lives beside the informer on the node agent — the device
only ever sees the dense padded window. Feature rows are computed with the
same formulas as `models.features.build_features` so a window's last
column equals what the single-tick estimators would have seen.

Not thread-safe by design — single-writer, same contract as the informer
(`docs/developer/power-attribution-guide.md:251-257` in the reference).
Where a reader runs beside the writer (the aggregator's window assembly
beside its ingest) the owner puts one lock around every `push()` and every
`window_arrays()` of a buffer; a window read under it is never torn.
"""

from __future__ import annotations

import numpy as np

from kepler_tpu.models.features import NUM_FEATURES
from kepler_tpu.resource.informer import FeatureBatch


def feature_rows(batch: FeatureBatch, dt_s: float) -> np.ndarray:
    """One tick's ``[W, F]`` feature matrix (numpy mirror of build_features)."""
    deltas = np.asarray(batch.cpu_deltas, np.float32)
    w = deltas.shape[0]
    denom = batch.node_cpu_delta
    share = deltas / denom if denom > 0 else np.zeros_like(deltas)
    rate = deltas / dt_s if dt_s > 0 else np.zeros_like(deltas)
    rows = np.empty((w, NUM_FEATURES), np.float32)
    rows[:, 0] = deltas
    rows[:, 1] = share
    rows[:, 2] = batch.usage_ratio
    rows[:, 3] = dt_s
    rows[:, 4] = rate
    rows[:, 5] = 1.0
    rows[:, 6] = np.log1p(max(denom, 0.0))
    return rows


# ``seen`` of a slot that holds no id: never old enough to evict
_NEVER = np.iinfo(np.int64).max


class HistoryBuffer:
    """Fixed-window per-id ring buffer of feature rows.

    ``evict_after``: drop ids not seen for that many pushes (terminated
    workloads; mirrors the informer's set-difference terminated detection).
    """

    def __init__(self, window: int = 32,
                 n_features: int = NUM_FEATURES,
                 evict_after: int = 2) -> None:
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = window
        self.n_features = n_features
        self._evict_after = evict_after
        self._tick = 0
        self._slab = np.zeros((0, window, n_features), np.float32)
        self._cursor = np.zeros(0, np.intp)  # next row to write, per slot
        self._count = np.zeros(0, np.intp)  # rows written, at most T
        self._seen = np.zeros(0, np.int64)  # tick of the last row
        self._slot: dict[str, int] = {}
        self._ids: list[str | None] = []  # slot → id
        self._free: list[int] = []
        self._grow()
        self._free.pop()  # slot 0, the empty window: never handed out
        # the last push's id list, its slot vector and its distinct ids
        self._last_ids: list[str] = []
        self._last_slots = np.zeros(0, np.intp)
        self._last_distinct = 0

    def __len__(self) -> int:
        return len(self._slot)

    def _grow(self) -> None:
        old = len(self._ids)
        new = max(2 * old, 16)
        slab = np.zeros((new, self.window, self.n_features), np.float32)
        slab[:old] = self._slab
        self._slab = slab
        self._cursor = np.concatenate(
            [self._cursor, np.zeros(new - old, np.intp)])
        self._count = np.concatenate(
            [self._count, np.zeros(new - old, np.intp)])
        self._seen = np.concatenate(
            [self._seen, np.full(new - old, _NEVER, np.int64)])
        self._ids.extend([None] * (new - old))
        self._free.extend(range(new - 1, old - 1, -1))  # lowest slot first

    def _take_slots(self, ids: list[str]) -> np.ndarray:
        """The slot of every id, a fresh (empty) one for an id not held."""
        slots = []
        for wid in ids:
            slot = self._slot.get(wid)
            if slot is None:
                if not self._free:
                    self._grow()
                slot = self._free.pop()
                self._slot[wid] = slot
                self._ids[slot] = wid
            slots.append(slot)
        return np.asarray(slots, np.intp)

    def _append(self, slots: np.ndarray, rows: np.ndarray) -> None:
        """One row onto each slot's ring; ``slots`` holds no slot twice."""
        cursor = self._cursor[slots]
        self._slab[slots, cursor] = rows
        self._cursor[slots] = (cursor + 1) % self.window
        self._count[slots] = np.minimum(self._count[slots] + 1, self.window)
        self._seen[slots] = self._tick

    def push(self, batch: FeatureBatch, dt_s: float) -> None:
        """Append this tick's row for every workload in the batch."""
        rows = feature_rows(batch, dt_s)
        self._tick += 1
        if batch.ids != self._last_ids:
            self._last_ids = list(batch.ids)
            self._last_slots = self._take_slots(self._last_ids)
            self._last_distinct = len(set(self._last_ids))
        slots = self._last_slots
        if self._last_distinct == len(slots):
            self._append(slots, rows)
        else:
            # an id listed twice gets both rows, in the batch's order
            for i in range(len(slots)):
                self._append(slots[i:i + 1], rows[i:i + 1])
        if self._evict_after > 0 and len(self._slot) > self._last_distinct:
            dead = np.flatnonzero(
                self._seen <= self._tick - self._evict_after)
            for slot in dead.tolist():
                del self._slot[self._ids[slot]]
                self._ids[slot] = None
                self._free.append(slot)
            # back to what a fresh slot holds: its next id starts empty
            self._slab[dead] = 0.0
            self._cursor[dead] = 0
            self._count[dead] = 0
            self._seen[dead] = _NEVER

    def window_arrays(
        self, ids: list[str],
        out: tuple[np.ndarray, np.ndarray] | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """→ (features f32 [W, T, F], t_valid bool [W, T]), right-padded.

        Rows are oldest→newest so the last valid position is the current
        tick — the position ``predict_temporal`` pools. Unknown ids yield
        empty (all-invalid) windows. ``out``: C-contiguous arrays of those
        shapes and dtypes to write into (and return) instead of new ones.
        """
        w, t = len(ids), self.window
        if out is None:
            out = (np.empty((w, t, self.n_features), np.float32),
                   np.empty((w, t), bool))
        feats, t_valid = out
        if ids == self._last_ids:
            slots = self._last_slots
        else:
            slots = np.fromiter((self._slot.get(wid, 0) for wid in ids),
                                np.intp, w)
        steps = np.arange(t)
        count = self._count[slots][:, None]
        # unroll the ring: oldest entry sits at the write cursor once full.
        # A ring not yet full starts at row 0 (cursor == count), and its
        # unwritten tail still holds the zeros the slot was created with.
        at = (self._cursor[slots][:, None] - count + steps) % t
        at += slots[:, None] * t
        # any mode but "raise" lets take() write straight into ``feats``
        np.take(self._slab.reshape(-1, self.n_features), at, axis=0,
                out=feats, mode="clip")
        np.less(steps, count, out=t_valid)
        return feats, t_valid
