"""Open-loop load generator: arrivals on a schedule drawn from the seed,
sent whether or not earlier requests have finished.

The schedule is a pure function of (seed, arrival parameters, seconds).
Latency counts from when a request was DUE to `Future.t_done`, so a stall
is charged to every request that waited behind it; how late the generator
itself ran (send time minus due time) is reported beside the latencies,
because a starved generator would otherwise read as a fast server.

Arrival parameters (a traffic file's `arrivals` object):
  rate_rps   mean arrivals per second
  burst      optional {"period_s", "duty", "peak_ratio"}: the rate
             alternates between a high phase (the first `duty` share of
             each period) and a low one, high/low = peak_ratio, mean kept.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


def due_times(seed: int, arrivals: Dict, seconds: float) -> np.ndarray:
    """Ascending due offsets in [0, seconds), a Poisson process (optionally
    with on/off bursts) from the seed alone."""
    rate = float(arrivals["rate_rps"])
    rng = np.random.default_rng([int(seed), 0xA221])
    n = int(rate * seconds * 1.5) + 64
    unit = np.cumsum(rng.exponential(1.0, size=n))  # unit-rate arrivals
    burst = arrivals.get("burst")
    if not burst:
        due = unit / rate
    else:
        # Time-warp through the inverse of the cumulative intensity.
        period, duty = float(burst["period_s"]), float(burst["duty"])
        ratio = float(burst["peak_ratio"])
        low = rate / (duty * ratio + (1.0 - duty))
        high = low * ratio
        edges, mass = [0.0], [0.0]
        t = 0.0
        while t < seconds:
            for span, r in ((duty * period, high), ((1 - duty) * period, low)):
                t += span
                edges.append(t)
                mass.append(mass[-1] + span * r)
        due = np.interp(unit, mass, edges, right=np.inf)
    return due[due < seconds]


class Sent:
    """One request as the generator saw it."""

    __slots__ = ("due", "sent", "future")

    def __init__(self, due: float, sent: float, future):
        self.due, self.sent, self.future = due, sent, future


def run_open_loop(
    submit: Callable,
    payloads: Sequence,
    due: np.ndarray,
    order: np.ndarray,
    *,
    refused: Tuple[type, ...] = (),
    lead_s: float = 0.05,
    tick: Optional[Callable[[float], None]] = None,
) -> Tuple[float, List[Sent]]:
    """Send payloads[order[i]] at t0 + due[i] on the monotonic clock (the
    clock `Future.t_done` is stamped on). A submit that raises one of
    `refused` is recorded with no future. `tick(seconds since t0)` runs
    between sends (the traced run stops its trace from it). Returns
    (t0, records)."""
    t0 = time.monotonic() + lead_s
    out: List[Sent] = []
    for d, k in zip(due.tolist(), order.tolist()):
        target = t0 + d
        while True:
            now = time.monotonic()
            if now >= target:
                break
            # Sleep most of a long gap; yield the interpreter in a short one.
            time.sleep(target - now - 2e-4 if target - now > 5e-4 else 0)
        try:
            fut = submit(payloads[k])
        except refused:
            fut = None
        out.append(Sent(target, now, fut))
        if tick is not None:
            tick(now - t0)
    return t0, out


def collect(records: List[Sent], timeout_s: float) -> Dict:
    """Wait for every future. Latencies (seconds, due -> t_done) of the
    requests that completed, sorted; the count that did not (refused at
    submit, failed, expired or still in flight at the timeout); and the
    generator's lateness per request, sorted."""
    deadline = time.monotonic() + timeout_s
    lat, failed = [], 0
    for r in records:
        if r.future is None:
            failed += 1
            continue
        try:
            r.future.result(max(deadline - time.monotonic(), 0))
            lat.append(r.future.t_done - r.due)
        except Exception:  # noqa: BLE001 — any failure is a failed request
            failed += 1
    return {"latency_s": sorted(lat), "failed": failed,
            "late_s": sorted(r.sent - r.due for r in records)}
