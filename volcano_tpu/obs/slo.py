"""SLO layer: per-lane latency windows with declared budgets and
error-budget burn tracking (ISSUE 13).

A *budget* declares "lane X's p99 stays under T ms, with at most
``allowed_frac`` of cycles over T" — the three shipped lanes are the
north-star trio: whole-cycle latency (``cycle``), the device lane
(``device``), and the idle-skip floor (``idle`` — cycles that
dispatched no solve must stay near the null-delta cost, or the "idle
is cheap" contract of the incremental lanes has silently rotted).

Tracking is a fixed sliding window (deque of the last ``window``
observations per lane) — bounded memory, exact percentiles over the
window, no decay math.  The *burn rate* is the classic error-budget
ratio: (violations / the CONFIGURED window size) / allowed_frac; a
burn rate >= 1.0 means the lane is consuming its error budget faster
than the SLO allows.  The denominator is deliberately the configured
window, not the filled portion: while the window is still filling,
each violation must be worth 1/window of budget, not 1/len — judging
a 10%-allowed budget over 16 early samples makes TWO expected fault
spikes an anomaly, which is exactly the startup flake the ISSUE 15
endurance pool leg exposed (clustered one-time jit compiles early in
the window fired edges a full window would absorb).  ``observe``
reports breach EDGES (enter-breach transitions, re-armed when the
window drops back under), so a sustained breach costs one anomaly,
not one per cycle; the auditor (obs/audit.py) turns those into
``slo-budget-exceeded`` anomalies.

Budgets come from env (``VOLCANO_TPU_SLO_CYCLE_P99_MS`` /
``VOLCANO_TPU_SLO_DEVICE_P99_MS`` / ``VOLCANO_TPU_SLO_IDLE_P99_MS`` /
``VOLCANO_TPU_SLO_TTB_P99_MS``,
unset = tracked but unbudgeted) or programmatically via ``declare`` —
the endurance harness declares explicit budgets and fails on burn.
The ``ttb`` lane is pod-centric, not cycle-centric: the journey log
(obs/journey.py, ISSUE 18) feeds one observation per first bind via
``observe_sample``.

Stdlib-only; internally synchronized (one small lock) so /debug reads
never contend the cycle thread for more than a dict copy.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from typing import Dict, List, Optional

DEFAULT_WINDOW = 256
# Minimum observations before a burn-rate breach can fire: percentile
# math over a handful of warmup cycles is noise, not signal.
MIN_SAMPLES = 16
DEFAULT_ALLOWED_FRAC = 0.01

_ENV_BUDGETS = (
    ("cycle", "VOLCANO_TPU_SLO_CYCLE_P99_MS"),
    ("device", "VOLCANO_TPU_SLO_DEVICE_P99_MS"),
    ("idle", "VOLCANO_TPU_SLO_IDLE_P99_MS"),
    # Pod time-to-bind (obs/journey.py, ISSUE 18): one observation per
    # FIRST bind, fed via observe_sample — the pod-centric SLO lane.
    ("ttb", "VOLCANO_TPU_SLO_TTB_P99_MS"),
)


class Budget:
    __slots__ = ("lane", "target_ms", "allowed_frac")

    def __init__(self, lane: str, target_ms: float,
                 allowed_frac: float = DEFAULT_ALLOWED_FRAC):
        self.lane = lane
        self.target_ms = float(target_ms)
        self.allowed_frac = max(float(allowed_frac), 1e-6)


def _pct(vals: List[float], q: float) -> float:
    vals = sorted(vals)
    i = min(int(q * (len(vals) - 1) + 0.5), len(vals) - 1)
    return vals[i]


def _breach(b: Budget, ms: float, window: List[float], burn: float,
            over: int) -> dict:
    """A breach edge's detail: the sample that crossed, the window it
    crossed in."""
    return {
        "lane": b.lane,
        "target_ms": b.target_ms,
        "observed_ms": round(ms, 3),
        "window_p99_ms": round(_pct(window, 0.99), 3),
        "burn_rate": round(burn, 2),
        "over_in_window": over,
        "window": len(window),
    }


class SLOTracker:
    """Per-lane sliding-window latency tracker with budget burn."""

    def __init__(self, window: int = DEFAULT_WINDOW):
        self.window = max(int(window), MIN_SAMPLES)
        self._lock = threading.Lock()
        self._lanes: Dict[str, deque] = {}  # guarded-by: _lock
        self.budgets: Dict[str, Budget] = {}  # guarded-by: _lock
        self._breached: Dict[str, bool] = {}  # guarded-by: _lock
        # Monotone per-lane violation counters (the burn *counters*; the
        # instantaneous burn *rate* is in snapshot()).
        self.violations: Dict[str, int] = {}  # guarded-by: _lock
        self.observations: Dict[str, int] = {}  # guarded-by: _lock
        for lane, env in _ENV_BUDGETS:
            raw = os.environ.get(env)
            if raw:
                try:
                    self.budgets[lane] = Budget(lane, float(raw))
                except ValueError:
                    pass

    def declare(self, lane: str, target_ms: float,
                allowed_frac: float = DEFAULT_ALLOWED_FRAC) -> None:
        with self._lock:
            self.budgets[lane] = Budget(lane, target_ms, allowed_frac)
            self._breached.pop(lane, None)

    # ------------------------------------------------------------ observe

    def observe(self, duration_s: float, lanes: Dict[str, float],
                idle: bool = False) -> List[dict]:
        """Feed one cycle; returns breach-edge dicts (possibly empty).
        ``lanes`` is the cycle's lane-seconds dict; ``idle`` marks a
        cycle that dispatched no solve (the idle-skip floor lane)."""
        obs = {"cycle": duration_s * 1e3}
        dev = lanes.get("device")
        if dev is not None:
            obs["device"] = dev * 1e3
        if idle:
            obs["idle"] = duration_s * 1e3
        breaches: List[dict] = []
        with self._lock:
            for lane, ms in obs.items():
                self._feed_locked(lane, ms, breaches)
        return breaches

    def observe_sample(self, lane: str, ms: float) -> List[dict]:
        """Feed one out-of-cycle observation (e.g. the journey's
        per-pod time-to-bind) into ``lane`` with the same budget /
        burn-rate / breach-edge semantics as ``observe``."""
        breaches: List[dict] = []
        with self._lock:
            self._feed_locked(lane, float(ms), breaches)
        return breaches

    def observe_samples(self, lane: str, values) -> List[dict]:
        """``observe_sample`` for a batch under one lock acquisition:
        the window, counters, gauge and breach edges (in order) that
        one call per value would leave."""
        import numpy as np

        vals = np.asarray(values, dtype=np.float64)
        breaches: List[dict] = []
        if not vals.size:
            return breaches
        with self._lock:
            win = self._lanes.get(lane)
            if win is None:
                win = self._lanes[lane] = deque(maxlen=self.window)
            b = self.budgets.get(lane)
            if b is not None:
                self._feed_many_locked(lane, b, win, vals, breaches)
            win.extend(vals[-self.window:].tolist())
            self.observations[lane] = (
                self.observations.get(lane, 0) + int(vals.size))
        return breaches

    # holds: _lock
    def _feed_many_locked(self, lane: str, b: Budget, win: deque, vals,
                          breaches: List[dict]) -> None:
        """The budget leg of ``_feed_locked`` for every value of a
        batch, ``win`` being the window before it: the count over the
        sliding window after each append is a difference of cumulative
        sums over window + batch."""
        import numpy as np

        from ..metrics import metrics

        old, n, size = len(win), len(vals), self.window
        seq = np.concatenate([np.fromiter(win, np.float64, old), vals])
        cum = np.concatenate([[0], np.cumsum(seq > b.target_ms)])
        self.violations[lane] = (
            self.violations.get(lane, 0) + int(cum[-1] - cum[old]))
        end = np.arange(old + 1, old + n + 1)  # window end, per append
        lo = np.maximum(end - size, 0)
        first = int(np.searchsorted(end - lo, MIN_SAMPLES))
        if first == n:
            return
        over = (cum[end] - cum[lo])[first:]
        burn = (over / size) / b.allowed_frac
        hot = burn >= 1.0
        was = np.concatenate([[self._breached.get(lane, False)], hot[:-1]])
        self._breached[lane] = bool(hot[-1])
        metrics.slo_burn_rate.set(round(float(burn[-1]), 4), lane=lane)
        for j in np.flatnonzero(hot & ~was).tolist():
            i = first + j
            breaches.append(_breach(b, float(vals[i]),
                                    seq[lo[i]:end[i]].tolist(),
                                    float(burn[j]), int(over[j])))

    # holds: _lock
    def _feed_locked(self, lane: str, ms: float,
                     breaches: List[dict]) -> None:
        from ..metrics import metrics

        win = self._lanes.get(lane)
        if win is None:
            win = self._lanes[lane] = deque(maxlen=self.window)
        win.append(ms)
        self.observations[lane] = (
            self.observations.get(lane, 0) + 1)
        b = self.budgets.get(lane)
        if b is None:
            return
        if ms > b.target_ms:
            self.violations[lane] = (
                self.violations.get(lane, 0) + 1)
        if len(win) < MIN_SAMPLES:
            return
        over = sum(1 for v in win if v > b.target_ms)
        # Burn over the CONFIGURED window (unfilled slots count
        # healthy) — see the module docstring.
        burn = (over / self.window) / b.allowed_frac
        was = self._breached.get(lane, False)
        now = burn >= 1.0
        self._breached[lane] = now
        metrics.slo_burn_rate.set(round(burn, 4), lane=lane)
        if now and not was:
            breaches.append(_breach(b, ms, list(win), burn, over))

    # ------------------------------------------------------------- reads

    def snapshot(self) -> dict:
        """The /debug/health "slo" section: per-lane p50/p99 over the
        window, declared budgets, burn rates, breach state."""
        with self._lock:
            lanes = {k: list(v) for k, v in self._lanes.items()}
            budgets = dict(self.budgets)
            breached = dict(self._breached)
            violations = dict(self.violations)
            observations = dict(self.observations)
        out = {}
        for lane, vals in sorted(lanes.items()):
            b = budgets.get(lane)
            entry = {
                "window": len(vals),
                "p50_ms": round(_pct(vals, 0.50), 3) if vals else None,
                "p99_ms": round(_pct(vals, 0.99), 3) if vals else None,
                "observations": observations.get(lane, 0),
            }
            if b is not None:
                over = sum(1 for v in vals if v > b.target_ms)
                burn = ((over / self.window) / b.allowed_frac
                        if vals else 0.0)
                entry.update({
                    "target_p99_ms": b.target_ms,
                    "allowed_frac": b.allowed_frac,
                    "violations_total": violations.get(lane, 0),
                    "burn_rate": round(burn, 4),
                    "breached": breached.get(lane, False),
                    "budget_remaining": round(max(1.0 - burn, 0.0), 4),
                })
            out[lane] = entry
        return out
