"""The benchmark's own evictor, the binder's sibling: where an eviction is
observed.

The store calls ``evict(pod)`` from the cycle thread (``ClusterStore.evict``
and the fast path's ``EvictState.flush``) after it has marked the pod
``deleting``.  Each eviction is stamped with ``perf_counter_ns`` on entry and
kept as it came, with the record the store handed over (the kubelet's side of
a cycle, ``loop.Driver``, ends the termination of that record); all reckoning
is done later, outside the timed spans.
"""

from __future__ import annotations

import threading
import time
from typing import List, Tuple


class RecordingEvictor:
    def __init__(self):
        self.lock = threading.Lock()
        # (arrival ns, key, the store's pod record), in arrival order.
        self.evictions: List[Tuple[int, str, object]] = []  # guarded by lock
        self.count = 0  # guarded by lock

    def evict(self, pod) -> None:
        t = time.perf_counter_ns()
        with self.lock:
            self.evictions.append((t, f"{pod.namespace}/{pod.name}", pod))
            self.count += 1
