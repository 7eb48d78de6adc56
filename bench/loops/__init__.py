"""Arrival loops: ``bench/loops/<loop>.py`` for a mix file whose ``loop``
key names it.  Each module gives ``drive(submit, traffic, t_open, seconds,
grace_s)``: it sends the traffic's requests through ``submit(keywords) ->
Future`` from ``t_open`` (a ``perf_counter`` time) for ``seconds``, waits up
to ``grace_s`` past the close for each answer, and returns one
:class:`Record` per request sent."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Record:
    client: int
    keywords: tuple
    sent: float                    # perf_counter at submit
    done: Optional[float] = None   # perf_counter when the answer returned
    response: object = None
    error: Optional[str] = None
