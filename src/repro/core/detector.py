# guardlint: hot  (fleet-sized arrays live here: float32, no per-node loops)
"""Peer-relative, multi-signal, temporally-filtered straggler detection (§4.2).

The detector never uses absolute thresholds. Every metric is scored against
the *peer baseline* of nodes in the same job via robust statistics
(median / MAD z-scores), which adapts to workload characteristics and
hardware heterogeneity for free. A node is flagged only when

  1. its PRIMARY signal (step_time) shows a sustained relative slowdown, OR
  2. multiple SUPPORTING hardware signals deviate together (pending
     verification tier — no step impact yet),

and the deviation persists for >= K of the last N evaluation windows
(temporal filter). Hysteresis: once flagged, a node needs ``clear_windows``
consecutive clean windows to unflag, preventing oscillation.

The hot path is array-native: ``StragglerDetector.update`` returns a
struct-of-arrays ``FleetAssessment`` whose latch / clean-streak state is
held as node-indexed arrays, so one 16k-node evaluation window costs a
fixed number of numpy reductions and O(flagged) Python objects — per-node
``NodeAssessment`` records are materialized lazily, and only for the
consumers that ask for them. Deviation counts accumulate in the smallest
unsigned type that holds the window: NumPy would widen the bool reduction
to int64 through a buffered cast (~3 ms a frame at 65,536 rows).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional

import numpy as np

from repro import tracing
from repro.core.telemetry import (HARDWARE_METRICS, METRIC_DIRECTION, Frame,
                                  RingHistory)
from repro.kernels.fleet_score import score_rows


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    """Defaults are the paper's 'moderately conservative' operating point
    (§6.1): aggressive enough to catch mild greys early (low FNR) at the
    price of a double-digit FPR — acceptable because early remediation
    stages are lightweight and reversible (Table 3: FPR 12.4%, FNR 7.8%)."""
    window: int = 6              # N: evaluation windows kept for filtering
    persistence: int = 3         # K: windows (of N) a signal must deviate
    z_threshold: float = 3.0     # robust z beyond which a signal deviates
    min_support: int = 2         # hardware signals required for hw-only flag
    slowdown_floor: float = 0.025  # relative step-time excess that counts
    stall_factor: float = 5.0    # step_time > stall_factor x median = stall
    clear_windows: int = 3       # hysteresis: clean windows to unflag
    mad_floor_frac: float = 0.01 # MAD floor as a fraction of the median
    scorer: str = "numpy"        # fleet_score backend: numpy | jax | pallas


@dataclasses.dataclass
class NodeAssessment:
    """Detector verdict for one node in one evaluation window."""
    node_id: int
    slowdown: float              # sustained relative step-time excess (>=0)
    stalled: bool
    support: List[str]           # hardware metrics in sustained deviation
    step_deviant: bool           # primary signal sustained deviation
    flagged: bool                # overall verdict after temporal filtering


class FleetAssessment:
    """One evaluation window's verdicts for the whole fleet, as arrays.

    Every field is aligned with ``node_ids``; per-node ``NodeAssessment``
    objects exist only when a consumer materializes them (``node``,
    ``flagged_assessments``, or the sequence protocol, which older
    callers use transparently). ``materialized`` counts how many were
    built — the scale benchmark asserts it stays O(flagged)."""

    __slots__ = ("node_ids", "slowdown", "stalled", "step_deviant",
                 "support_masks", "flagged", "materialized", "_index")

    def __init__(self, node_ids: np.ndarray, slowdown: np.ndarray,
                 stalled: np.ndarray, step_deviant: np.ndarray,
                 support_masks: Dict[str, np.ndarray],
                 flagged: np.ndarray):
        self.node_ids = node_ids
        self.slowdown = slowdown
        self.stalled = stalled
        self.step_deviant = step_deviant
        self.support_masks = support_masks
        self.flagged = flagged
        self.materialized = 0
        self._index: Optional[Dict[int, int]] = None

    # ------------------------------------------------------ array queries

    def flagged_indices(self) -> np.ndarray:
        return np.flatnonzero(self.flagged)

    def flagged_ids(self) -> np.ndarray:
        return self.node_ids[self.flagged]

    def index_of(self, node_id: int) -> Optional[int]:
        # vectorized scan: callers look up O(flagged) ids per window, so a
        # full dict build would dwarf the lookups themselves
        hit = np.flatnonzero(self.node_ids == node_id)
        return int(hit[0]) if hit.size else None

    def flagged_of(self, node_id: int) -> Optional[bool]:
        """Latched verdict for one node id; None if not in this frame."""
        i = self.index_of(node_id)
        return None if i is None else bool(self.flagged[i])

    # ---------------------------------------------- lazy materialization

    def support_of(self, i: int) -> List[str]:
        return [m for m, msk in self.support_masks.items() if msk[i]]

    def node(self, i: int) -> NodeAssessment:
        """Materialize the per-node record for row ``i``."""
        self.materialized += 1
        return NodeAssessment(
            node_id=int(self.node_ids[i]),
            slowdown=float(self.slowdown[i]),
            stalled=bool(self.stalled[i]),
            support=self.support_of(i),
            step_deviant=bool(self.step_deviant[i]),
            flagged=bool(self.flagged[i]),
        )

    def flagged_assessments(self) -> List[NodeAssessment]:
        return [self.node(int(i)) for i in self.flagged_indices()]

    # -------------------------------------------------- sequence protocol
    # Compatibility with the pre-vectorization API, where update()
    # returned List[NodeAssessment]: indexing/iteration materialize
    # records on demand, so old-style consumers keep working while the
    # hot path stays allocation-free.

    def __len__(self) -> int:
        return len(self.node_ids)

    def __getitem__(self, i: int) -> NodeAssessment:
        return self.node(i)

    def __iter__(self) -> Iterator[NodeAssessment]:
        # guardlint: disable=GL003 reason=compat sequence protocol for
        # old-style consumers; the hot path reads the arrays directly
        for i in range(len(self.node_ids)):
            yield self.node(i)


def robust_z(values: np.ndarray, axis: int = -1,
             mad_floor: float = 1e-9) -> np.ndarray:
    """Median/MAD z-score along ``axis`` (peer axis). 0.6745 ~ Φ⁻¹(3/4)."""
    med = np.median(values, axis=axis, keepdims=True)
    mad = np.median(np.abs(values - med), axis=axis, keepdims=True)
    scale = np.maximum(mad / 0.6745, mad_floor)
    return (values - med) / scale


class StragglerDetector:
    """Stateful fleet-wide detector; feed one Frame per evaluation window."""

    def __init__(self, cfg: Optional[DetectorConfig] = None):
        self.cfg = cfg or DetectorConfig()
        self.history = RingHistory(self.cfg.window)
        # latch / clean-streak state as node-indexed arrays aligned with
        # the last frame's node_ids; ids that left the frame park their
        # state in _off until reset_node forgets them (same semantics as
        # the old per-id dicts, without per-window dict traffic)
        self._state_ids: Optional[np.ndarray] = None
        self._latched: Optional[np.ndarray] = None
        self._clean: Optional[np.ndarray] = None
        self._off: Dict[int, tuple] = {}   # id -> (latched, clean_streak)
        # per-row score caches aligned with the ring buffers: each history
        # row's peer-relative deviation verdicts never change once scored
        # (peer medians are within-row), so one window costs one new row of
        # medians instead of depth x metrics of them. Replacement backfill
        # and reallocation rescore everything (rare). All caches are
        # float32 end-to-end — the fleet_score kernel contract.
        self._gen = -1                      # history generation scored
        self._dev3: Optional[np.ndarray] = None  # (M, depth, N) bool
        self._rel: Optional[np.ndarray] = None  # (depth, N) step_time rel
        self._contrib: Optional[np.ndarray] = None  # (depth, N) masked rel
        self._metric_list: List[str] = []
        self._dirs: tuple = ()
        self._st_j: Optional[int] = None
        self._rows_mat: Optional[np.ndarray] = None  # (depth, M, N) scratch
        # counts never exceed the window: uint8 up to 255, uint16 above
        self._count_t = np.min_scalar_type(self.history.depth)

    # ------------------------------------------------------------ core

    def _score_rows(self, rows: np.ndarray) -> None:
        """Score ring-buffer rows (peer-relative robust-z deviation +
        step-time relative excess) in one fused (R, M, N) pass through
        ``repro.kernels.fleet_score`` — every op reduces along the peer
        axis independently, so batching rows changes no verdict."""
        cfg = self.cfg
        mats = self._rows_mat[:len(rows)]          # (R, M, N) f32 scratch
        for j, m in enumerate(self._metric_list):
            mats[:, j] = self.history.rows_raw(m)[rows]
        dev, rel, contrib = score_rows(
            mats, self._dirs, self._st_j,
            z_threshold=cfg.z_threshold, slowdown_floor=cfg.slowdown_floor,
            mad_floor_frac=cfg.mad_floor_frac, backend=cfg.scorer)
        self._dev3[:, rows] = np.swapaxes(dev, 0, 1)
        if self._st_j is not None:
            self._rel[rows] = rel
            self._contrib[rows] = contrib

    def _sync_scores(self) -> None:
        """Bring the per-row caches up to date after a push."""
        hist = self.history
        if hist.generation != self._gen:
            self._gen = hist.generation
            n = len(hist.last().node_ids)
            m = len(hist.metric_names())
            self._metric_list = list(hist.metric_names())
            self._dirs = tuple(float(METRIC_DIRECTION[k])
                               for k in self._metric_list)
            self._metric_idx = {k: j
                                for j, k in enumerate(self._metric_list)}
            self._st_j = self._metric_idx.get("step_time")
            self._rows_mat = np.empty((hist.depth, m, n), np.float32)
            self._dev3 = np.empty((m, hist.depth, n), bool)
            self._rel = np.empty((hist.depth, n), np.float32)
            self._contrib = np.empty((hist.depth, n), np.float32)
            rows = np.arange(len(hist))
        elif hist.last_backfill is not None:
            rows = np.arange(len(hist))      # backfill rescored everything
        else:
            rows = np.asarray([hist.last_row])
        tracing.count("detector.rows_scored", len(rows))
        self._score_rows(rows)

    def _realign_state(self, node_ids: np.ndarray) -> None:
        """Carry latch state over a fleet membership change by id."""
        old_ids, old_latch, old_clean = \
            self._state_ids, self._latched, self._clean
        n = len(node_ids)
        self._latched = np.zeros(n, bool)
        self._clean = np.zeros(n, np.int64)
        if old_ids is not None and len(old_ids) == n:
            # typical case: a few replaced columns — bulk-copy the rest
            same = old_ids == node_ids
            self._latched[same] = old_latch[same]
            self._clean[same] = old_clean[same]
            moved = np.flatnonzero(~same)
        elif old_ids is not None:
            moved = np.arange(len(old_ids))
        else:
            moved = np.arange(0)
        for i in moved:                       # departing ids park in _off
            self._off[int(old_ids[i])] = (bool(old_latch[i]),
                                          int(old_clean[i]))
        if self._off:
            joins = moved if old_ids is not None and len(old_ids) == n \
                else np.arange(n)
            for i in joins:                   # rejoining ids resume state
                st = self._off.pop(int(node_ids[i]), None)
                if st is not None:
                    self._latched[i], self._clean[i] = st
        self._state_ids = node_ids.copy()

    def update(self, frame: Frame) -> FleetAssessment:
        with tracing.span("detector.push"):
            self.history.push(frame)
        with tracing.span("detector.score"):
            self._sync_scores()
        with tracing.span("detector.verdicts"):
            return self._verdicts(frame)

    def _verdicts(self, frame: Frame) -> FleetAssessment:
        """Counts, slowdown, stalls, support and the latch over the
        scored history, for the frame just pushed."""
        cfg = self.cfg
        depth = len(self.history)
        used = slice(0, depth)
        # "sustained" requires a full persistence window of history; until
        # then only stalls can flag (fresh jobs / post-replacement re-baseline)
        warmed = depth >= cfg.persistence
        need = cfg.persistence if warmed else depth + 1  # unattainable early

        # --- primary signal: sustained relative step-time excess
        # (one stacked reduction covers every metric's deviation counts,
        # summed straight off the bool cache in the window's narrow type)
        all_counts = self._dev3[:, used].sum(1, dtype=self._count_t)  # (M, N)
        dev_count = all_counts[self._st_j]
        step_deviant = dev_count >= need
        # sustained slowdown magnitude: mean over deviant windows. The
        # masked sum runs in chronological window order so it is
        # bit-stable against the ring buffer's write position.
        order = self.history._order()
        slow_sum = self._contrib[order].sum(0)
        slowdown = np.where(
            step_deviant,
            slow_sum / np.maximum(dev_count, 1).astype(np.float32),
            np.float32(0.0))

        # --- stalls: no heartbeat or grossly inflated latest step
        last = self.history.last()
        stalled = (~last.valid) | (
            last.metrics["step_time"] >
            cfg.stall_factor * np.median(last.metrics["step_time"]))

        # --- supporting hardware signals (sustained)
        support_masks = {}
        support_count = np.zeros(len(frame.node_ids), dtype=self._count_t)
        for m in HARDWARE_METRICS:
            if m in self._metric_idx:
                mask = all_counts[self._metric_idx[m]] >= need
                support_masks[m] = mask
                support_count += mask

        raw_flag = stalled | step_deviant | (support_count >= cfg.min_support)

        # --- hysteresis latch, vectorized over node-indexed state arrays
        if self._state_ids is None or \
                not np.array_equal(self._state_ids, frame.node_ids):
            self._realign_state(frame.node_ids)
        latched, clean = self._latched, self._clean
        clean[:] = np.where(raw_flag, 0,
                            np.where(latched, clean + 1, clean))
        latched[:] = raw_flag | (latched & (clean < cfg.clear_windows))

        return FleetAssessment(
            node_ids=frame.node_ids, slowdown=slowdown, stalled=stalled,
            step_deviant=step_deviant, support_masks=support_masks,
            flagged=latched.copy())

    # ------------------------------------------------------- latch queries

    def latched_many(self, ids: np.ndarray) -> np.ndarray:
        """Vectorized ``is_latched`` over an id array: O(latched + len)
        numpy set membership instead of one fleet scan (or one Python
        set probe) per query."""
        ids = np.asarray(ids)
        out = np.zeros(len(ids), bool)
        if self._state_ids is not None and self._latched.any():
            out |= np.isin(ids, self._state_ids[self._latched])
        off_lat = [n for n, st in self._off.items() if st[0]]
        if off_lat:
            out |= np.isin(ids, np.asarray(off_lat, dtype=ids.dtype))
        return out

    def is_latched(self, node_id: int) -> bool:
        """Public latch query: is this node currently flagged (with
        hysteresis)? The health manager's deferred-swap confirmation and
        any external trace/UI consumer must use this instead of reaching
        into detector internals."""
        if self._state_ids is not None:
            hit = np.flatnonzero(self._state_ids == node_id)
            if hit.size:
                return bool(self._latched[hit[0]])
        st = self._off.get(int(node_id))
        return bool(st[0]) if st is not None else False

    def latched_nodes(self) -> List[int]:
        """All currently latched node ids (sorted, for stable iteration)."""
        ids = set()
        if self._state_ids is not None:
            ids.update(int(n) for n in self._state_ids[self._latched])
        ids.update(n for n, st in self._off.items() if st[0])
        return sorted(ids)

    def memory_nbytes(self) -> int:
        """Resident detector footprint: ring buffers, score caches,
        scratch and latch arrays (the scale benchmark's memory report)."""
        total = self.history.nbytes
        for a in (self._rows_mat, self._dev3, self._rel, self._contrib,
                  self._latched, self._clean, self._state_ids):
            if a is not None:
                total += a.nbytes
        return total

    def reset_node(self, node_id: int) -> None:
        """Forget latch state (node replaced/repaired)."""
        self._off.pop(int(node_id), None)
        if self._state_ids is not None:
            hit = np.flatnonzero(self._state_ids == node_id)
            if hit.size:
                self._latched[hit[0]] = False
                self._clean[hit[0]] = 0
