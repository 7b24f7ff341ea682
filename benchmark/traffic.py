"""The one general load generator. A traffic mix is a data file
(``traffic/<name>.json``); nothing here knows a mix by name.

Fields of a mix:

``generator_sleep_s``
    the source generator's sleep between ticks (environmentd's
    ``--tick-interval``): the update load.
``readers`` / ``reads_per_s`` / ``read_statement``
    open-loop readers: ``readers`` pgwire connections, together
    ``reads_per_s`` reads a second, each connection at a fixed period.
    ``{view}`` in the statement is the configuration's judged view.
    0 readers: no reads.
``schedule``
    ``"fixed_period_seeded_phase"``: the only kind so far. The
    connections' phases are the evenly spaced offsets ``i / readers`` of
    one period, dealt to the connections in an order drawn from the
    seed and shifted together by a seeded fraction of the spacing: every
    seed offers the same arrivals, in another order.
``warmup``
    ``catch_up``: the window opens only once the view has caught up
    with the sources; ``min_view_ticks`` / ``quiet_ticks``: the view
    has advanced that many ticks since hydration, the last
    ``quiet_ticks`` of them with no new compile record; ``warm_reads``:
    reads sent before the window so that their programs exist.
``end_to_end``
    the end-to-end metrics the mix reports.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SCHEDULES = ("fixed_period_seeded_phase",)


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no traffic mix {name!r}: {path}")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("readers", 0) and mix.get("schedule") not in SCHEDULES:
        raise ValueError(
            f"traffic mix {name!r}: unknown schedule {mix.get('schedule')!r}"
        )
    return mix


def read_schedule(mix: dict, seed: int, seconds: float) -> list:
    """Per connection, the offsets from the window's opening at which
    its reads are due: ``[[offset, ...], ...]``."""
    n = int(mix.get("readers", 0))
    if n == 0:
        return []
    period = n / float(mix["reads_per_s"])
    rng = random.Random(seed)
    slots = list(range(n))
    rng.shuffle(slots)
    shift = rng.random() * period / n
    out = []
    for slot in slots:
        phase = slot * period / n + shift
        due, k = [], 0
        while phase + k * period < seconds:
            due.append(phase + k * period)
            k += 1
        out.append(due)
    return out


class Readers:
    """Open-loop readers: each connection sends its reads at their due
    instants (or as soon after as its previous read has returned), and
    brackets every read with the sources' newest complete time before
    the send and after the last row."""

    def __init__(self, connect, statement: str, schedule: list,
                 newest_complete_time):
        self.connect = connect  # () -> client with .query(sql)/.close()
        self.statement = statement
        self.schedule = schedule
        self.newest = newest_complete_time  # () -> int
        self.reads: list = []
        self._threads: list = []

    def start(self, t_open: float) -> None:
        for offsets in self.schedule:
            recs = [
                {"due": t_open + off, "sent": None, "done": None,
                 "rows": None, "lo": None, "hi": None, "error": None}
                for off in offsets
            ]
            self.reads.extend(recs)  # every read DUE, answered or not
            th = threading.Thread(
                target=self._run, args=(recs,), daemon=True
            )
            th.start()
            self._threads.append(th)

    def _run(self, recs: list) -> None:
        client = None
        for rec in recs:
            due = rec["due"]
            try:
                # the bracket's low end is read just BEFORE the due
                # instant, outside the timed stretch
                wait = due - 0.004 - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                if client is None:
                    client = self.connect()
                rec["lo"] = self.newest()
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                rec["sent"] = time.monotonic()
                rows = client.query(self.statement)
                done = time.monotonic()
                rec["hi"] = self.newest()
                rec["rows"] = rows
                rec["done"] = done  # set last: the read is complete
            except Exception as e:  # a failed read is counted, not fatal
                rec["error"] = f"{type(e).__name__}: {e}"
                if client is not None:
                    client.close()
                    client = None
        if client is not None:
            client.close()

    def join(self, deadline: float) -> list:
        """Wait for every connection until ``deadline`` (monotonic).
        Returns every read that was DUE; one with ``done`` None failed
        or was still unanswered at the deadline."""
        for th in self._threads:
            th.join(max(deadline - time.monotonic(), 0))
        return [dict(r) for r in self.reads]
