"""The comparison that decides ``correct``.

What a window produced is judged against the plain reference
(``references/<name>.py``), recomputed from the source shards:

* the judged view's DURABLE shard: at every time the view's upper
  passed inside the window, the shard's contents at that time must be
  exactly the reference's answer over the sources at that time;
* every read the window sent: a read that was answered must be exactly
  the reference's answer at ONE time inside its bracket, which runs from
  the sources' newest complete time just before the read was sent (a
  fresh read may not be staler than that) to the newest complete time
  just after its last row arrived. A read that failed or was never
  answered (the runner waits a minute past the close) counts against
  ``correct``; a late one is only late.

* the source shards themselves: at the window's first and last time
  the relations the view reads must hold exactly the rows that the
  configuration's base tables, made again from the seed by a copy of the
  generator's arithmetic that imports nothing of the program
  (``references/<tables>.py``), hold at that time. The reference above
  takes its inputs from those shards through the program's persist
  codec, so a wrong source append or decode would otherwise pass on
  both sides.

Every comparison is exact, so every limit is 0: the configurations
state exact decimals and one timestamp an answer.

The functions here take plain data (numpy columns, lists of tuples), so
``tests/test_faults.py`` drives them with the timed path broken
underneath and no server.
"""

from __future__ import annotations

import decimal
import importlib.util
import os
import random

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reference(name: str):
    path = os.path.join(HERE, "references", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no reference {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Timeline:
    """A relation's history as one time-ordered array per column: the
    collection at time ``t`` is the prefix of updates with time <= t."""

    def __init__(self, base: dict, updates: dict):
        order = np.argsort(updates["time"], kind="stable")
        self.cols = {
            k: np.concatenate([np.asarray(base[k]), np.asarray(v)[order]])
            for k, v in updates.items()
            if k != "time"
        }
        self.times = np.concatenate(
            [
                np.full(len(base["diff"]), -1, np.int64),
                np.asarray(updates["time"], np.int64)[order],
            ]
        )

    def at(self, t: int) -> dict:
        n = int(np.searchsorted(self.times, t, side="right"))
        return {k: v[:n] for k, v in self.cols.items()}

    def count_between(self, lo: int, hi: int) -> int:
        """Updates with lo <= time < hi."""
        a = np.searchsorted(self.times, lo, side="left")
        b = np.searchsorted(self.times, hi, side="left")
        return int(b - a)


def _add(acc: dict, row: tuple, d: int) -> None:
    n = acc.get(row, 0) + d
    if n:
        acc[row] = n
    else:
        acc.pop(row, None)  # zero counts are dropped


def rows_of(table: dict, columns: tuple) -> dict:
    """A collection as a multiset ``{row: count}``."""
    acc: dict = {}
    cols = [np.asarray(table[c]).tolist() for c in columns]
    for *row, d in zip(*cols, np.asarray(table["diff"]).tolist()):
        _add(acc, tuple(row), d)
    return acc


def multiset(rows: list) -> dict:
    acc: dict = {}
    for r in rows:
        acc[r] = acc.get(r, 0) + 1
    return acc


def wire_rows(rows: list, ref) -> list:
    """Rows as a client got them (text fields) -> the reference's form:
    decimals as unscaled integers at the reference's scale, other
    numbers as ints, strings as they are."""
    out = []
    for r in rows:
        row = []
        for name, v in zip(ref.COLUMNS, r):
            scale = ref.DECIMAL_SCALE.get(name)
            if v is None:
                row.append(None)
            elif scale is not None:
                row.append(int(decimal.Decimal(v).scaleb(scale)))
            else:
                try:
                    row.append(int(v))
                except ValueError:
                    row.append(v)
        out.append(tuple(row))
    return sorted(out)


MAX_TIMES = 400  # view times recomputed in one run; more are sampled


def sample_times(first: int, end: int, seed: int) -> set:
    """The window's view times to recompute: all of them up to
    ``MAX_TIMES``, else a sample drawn from the seed with the first and
    the last in it. The shard's contents are carried through EVERY
    time, so an update that is wrong at a time left out is still held
    at the next time that is compared."""
    times = list(range(first, end))
    if len(times) <= MAX_TIMES:
        return set(times)
    picked = set(random.Random(seed).sample(times, MAX_TIMES - 2))
    return picked | {first, end - 1}


def judge(
    ref,
    sources: dict,
    view_base: dict,
    view_updates: dict,
    window_times: tuple,
    reads: list,
    precision: str = "exact",
    seed: int = 0,
    regenerated: dict | None = None,
) -> dict:
    """The numbers compared, each with its limit.

    ``sources``: ``{relation: Timeline}``. ``view_base`` /
    ``view_updates``: the view shard's contents just before the first
    window time and its updates from then on (plain columns, ``time``,
    ``diff``). ``window_times``: ``(first, end)``, the times the view's
    upper passed inside the window. ``reads``: ``[(lo, hi, rows|None)]``
    with ``rows`` already in the reference's form.

    ``precision`` other than ``"exact"`` puts the lower-precision
    control in the reference's place: it must fail. ``regenerated``:
    ``{time: {relation: {column: array}}}``, the base tables made again
    from the seed (every row once) at the times to hold the source
    shards to.
    """
    memo: dict = {}

    def want(t: int) -> dict:
        if t not in memo:
            tables = {name: tl.at(t) for name, tl in sources.items()}
            memo[t] = multiset(ref.answer(tables, precision))
        return memo[t]

    first, end = window_times
    wrong = empty = 0
    first_wrong = None
    held = rows_of(view_base, ref.COLUMNS)
    order = np.argsort(view_updates["time"], kind="stable")
    utime = np.asarray(view_updates["time"], np.int64)[order]
    ucols = [np.asarray(view_updates[c])[order].tolist() for c in ref.COLUMNS]
    udiff = np.asarray(view_updates["diff"], np.int64)[order].tolist()
    k = 0
    compared = sample_times(first, end, seed)
    for t in range(first, end):
        while k < len(utime) and utime[k] <= t:
            _add(held, tuple(c[k] for c in ucols), udiff[k])
            k += 1
        if t not in compared:
            continue
        expected = want(t)
        if not expected:
            empty += 1
        if held != expected:
            wrong += 1
            if first_wrong is None:
                first_wrong = {
                    "time": t,
                    "shard": sorted(held.items())[:4],
                    "reference": sorted(expected.items())[:4],
                }
    unanswered = wrong_reads = 0
    first_wrong_read = None
    for lo, hi, rows in reads:
        if rows is None:
            unanswered += 1
            continue
        got = multiset(rows)
        if not any(got == want(t) for t in range(lo, hi + 1)):
            wrong_reads += 1
            if first_wrong_read is None:
                first_wrong_read = {
                    "bracket": [lo, hi], "answer": rows[:4],
                    "reference_at_lo": sorted(want(lo).items())[:4],
                }
    source_wrong = 0
    first_wrong_source = None
    for t, tables in sorted((regenerated or {}).items()):
        for rel, table in tables.items():
            columns = tuple(table)
            got = rows_of(sources[rel].at(t), columns)
            made = rows_of(
                {**table, "diff": np.ones(len(table[columns[0]]), np.int64)},
                columns,
            )
            differ = [
                r for r in set(got) | set(made)
                if got.get(r, 0) != made.get(r, 0)
            ]
            source_wrong += len(differ)
            if differ and first_wrong_source is None:
                r = min(differ)
                first_wrong_source = {
                    "time": t, "relation": rel, "row": list(r),
                    "shard": got.get(r, 0), "regenerated": made.get(r, 0),
                }
    checks = {
        "source_rows_wrong": {"value": source_wrong, "limit": 0},
        "view_times_wrong": {"value": wrong, "limit": 0},
        "view_times_unchecked": {
            # a window in which the view never advanced, or whose
            # reference is empty, has proved nothing
            "value": (1 if end <= first else 0) + empty, "limit": 0,
        },
        "reads_wrong": {"value": wrong_reads, "limit": 0},
        "reads_unanswered": {"value": unanswered, "limit": 0},
    }
    return {
        "checks": checks,
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "view_times_checked": len(compared),
        "reads_checked": len(reads) - unanswered,
        "first_wrong_time": first_wrong,
        "first_wrong_read": first_wrong_read,
        "first_wrong_source_row": first_wrong_source,
    }
