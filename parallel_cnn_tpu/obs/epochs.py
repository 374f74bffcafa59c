"""The newest epoch records of this process: what `train/zoo.py:train`
hands its `metrics.record` at the end of an epoch (loss, seconds, where
the work lives, compile requests, a model's own counters), kept here too.
A caller's recorder keeps what it chooses; a reader that was handed
neither the recorder nor the record (the benchmark's per-layer readers see
their runner's selection of counters) finds the program's newest here,
the way it finds the compile log (:mod:`.compiles`) and the program
catalog (:mod:`.programs`). Always on: one small dict an epoch, the newest
`KEEP` of them.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Deque, Dict, List

KEEP = 64

_LOCK = threading.Lock()
_RECORDS: Deque[Dict[str, Any]] = collections.deque(maxlen=KEEP)


def record(rec: Dict[str, Any]) -> None:
    with _LOCK:
        _RECORDS.append(dict(rec))


def newest(n: int = 1) -> List[Dict[str, Any]]:
    """The newest `n` records, oldest first (fewer if fewer were made)."""
    with _LOCK:
        return list(_RECORDS)[-n:] if n > 0 else []


def clear() -> None:
    with _LOCK:
        _RECORDS.clear()
