"""Closed loop: each client sends its next request when its previous answer
is back; every client sends its first at the window's open, and nothing is
sent after ``t_open + seconds``."""
from __future__ import annotations

import threading
import time
from typing import List

from bench.loops import Record


def drive(submit, traffic, t_open: float, seconds: float,
          grace_s: float) -> List[Record]:
    t_close = t_open + seconds
    records: List[List[Record]] = [[] for _ in range(traffic.clients)]
    start = threading.Barrier(traffic.clients)

    def client(i: int) -> None:
        stream = traffic.client(i)
        start.wait()
        while time.perf_counter() < t_close:
            kws = next(stream)
            rec = Record(i, kws, time.perf_counter())
            records[i].append(rec)
            try:
                fut = submit(kws)
                wait = max(1.0, t_close + grace_s - time.perf_counter())
                rec.response = fut.result(timeout=wait)
                rec.done = time.perf_counter()
            except Exception as exc:  # a failed request is counted, not fatal
                rec.error = f"{type(exc).__name__}: {exc}"
                return

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(traffic.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + grace_s + 30)
    return [r for rs in records for r in rs]
