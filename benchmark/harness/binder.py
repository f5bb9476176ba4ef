"""The benchmark's own binder: where a bind is observed.

The store calls ``bind_keys`` (the fast path's batched dispatch, from the
program's bind dispatcher thread), ``bind_batch`` or ``bind``.  Each arrival
is stamped with ``perf_counter_ns`` on entry and kept as it came; all
reckoning is done later, outside the timed spans.
"""

from __future__ import annotations

import threading
import time
from typing import List, Tuple


class RecordingBinder:
    def __init__(self):
        self.cond = threading.Condition()
        # (arrival ns, keys, hosts), in arrival order.  guarded by cond
        self.arrivals: List[Tuple[int, list, list]] = []
        self.count = 0  # guarded by cond

    def bind_keys(self, keys, hostnames) -> None:
        t = time.perf_counter_ns()
        keys = list(keys)
        hostnames = list(hostnames)
        with self.cond:
            self.arrivals.append((t, keys, hostnames))
            self.count += len(keys)
            self.cond.notify_all()

    def bind_batch(self, pairs) -> None:
        pairs = list(pairs)
        self.bind_keys([f"{t.namespace}/{t.name}" for t, _ in pairs],
                       [h for _, h in pairs])

    def bind(self, task, hostname) -> None:
        self.bind_keys([f"{task.namespace}/{task.name}"], [hostname])

    def wait_for(self, count: int, timeout: float) -> bool:
        """Block until ``count`` binds have arrived in all; False on timeout."""
        deadline = time.monotonic() + timeout
        with self.cond:
            while self.count < count:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.cond.wait(left)
        return True
