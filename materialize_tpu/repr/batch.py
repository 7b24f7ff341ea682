"""Columnar update batches: the device representation of a chunk of a
time-varying collection.

Every collection flows as ``(data, time, diff)`` update triples
(reference: doc/developer/platform/formalism.md:5-25). On TPU the unit of
flow is a fixed-capacity columnar batch: struct-of-arrays data columns plus
``time`` (u64) and ``diff`` (i64) columns and a scalar ``count`` of valid
rows. Rows [0, count) are valid; the tail is padding. Fixed capacities keep
XLA shapes static (SURVEY.md §7 hard part #1); overflow is detected on
device and resolved host-side by retrying at a larger capacity tier.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .schema import DIFF_DTYPE, TIME_DTYPE, Column, ColumnType, Schema


def capacity_tier(n: int, minimum: int = 256) -> int:
    """Round up to the capacity tier (power of two) for compile caching."""
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


@jax.tree_util.register_pytree_node_class
@dataclass
class Batch:
    """A fixed-capacity columnar chunk of (data, time, diff) updates.

    cols  : tuple of [cap]-shaped arrays, one per schema column
    nulls : tuple of ([cap] bool array | None), one per schema column
    time  : [cap] uint64
    diff  : [cap] int64
    count : scalar int32 — rows [0, count) are valid
    schema: static aux data (host-side)
    """

    cols: tuple
    nulls: tuple
    time: jnp.ndarray
    diff: jnp.ndarray
    count: jnp.ndarray
    schema: Schema
    # Static producer guarantees (trace-time facts; part of the pytree
    # aux so jit compiles hint-specialized programs). Known hint:
    # "hash_consolidated" — rows sorted by the hash-pair order of their
    # content (ops/lanes.hash_pair), at most one row per content,
    # nonzero diffs. Host producers (load generators) pre-sort with the
    # numpy replica (hash_pair_host), letting the device skip input
    # sorts — sort EXECUTION on TPU is ~2us/row at 32k+, the input-side
    # cost ceiling for large micro-batches.
    hints: tuple = ()

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        null_present = tuple(n is not None for n in self.nulls)
        children = (
            self.cols,
            tuple(n for n in self.nulls if n is not None),
            self.time,
            self.diff,
            self.count,
        )
        return children, (self.schema, null_present, self.hints)

    @classmethod
    def tree_unflatten(cls, aux, children):
        schema, null_present, hints = aux
        cols, nulls_packed, time, diff, count = children
        nulls = []
        it = iter(nulls_packed)
        for present in null_present:
            nulls.append(next(it) if present else None)
        return cls(
            tuple(cols), tuple(nulls), time, diff, count, schema, hints
        )

    # -- properties --------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.diff.shape[0]

    def valid_mask(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.count

    def col(self, name: str) -> jnp.ndarray:
        return self.cols[self.schema.index_of(name)]

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_numpy(
        schema: Schema,
        cols: Sequence[np.ndarray],
        time,
        diff,
        capacity: int | None = None,
        nulls: Sequence[np.ndarray | None] | None = None,
        hints: tuple = (),
    ) -> "Batch":
        """Build a Batch from host arrays, padding up to a capacity tier."""
        cols = [np.asarray(c) for c in cols]
        n = len(diff) if np.ndim(diff) else (cols[0].shape[0] if cols else 0)
        cap = capacity if capacity is not None else capacity_tier(max(n, 1))
        assert cap >= n, f"capacity {cap} < rows {n}"

        def pad(a, dtype):
            a = np.asarray(a, dtype=dtype)
            if a.ndim == 0:
                a = np.full(n, a, dtype=dtype)
            out = np.zeros(cap, dtype=dtype)
            out[:n] = a
            return jnp.asarray(out)

        dev_cols = tuple(
            pad(c, col.dtype) for c, col in zip(cols, schema.columns)
        )
        if nulls is None:
            nulls = [None] * len(schema.columns)
        dev_nulls = tuple(
            (pad(nl, np.bool_) if nl is not None else None) for nl in nulls
        )
        b = Batch(
            cols=dev_cols,
            nulls=dev_nulls,
            time=pad(time, TIME_DTYPE),
            diff=pad(diff, DIFF_DTYPE),
            count=jnp.asarray(n, dtype=jnp.int32),
            schema=schema,
            hints=hints,
        )
        # Host-known row count for staging/benchmark code: reading
        # `count` back from the device is a d2h transfer that blocks
        # on everything dispatched before it, serializing the
        # pipeline. Not a pytree field; lost on tree transforms.
        b._host_count = n
        return b

    @staticmethod
    def empty(schema: Schema, capacity: int = 256) -> "Batch":
        return Batch.from_numpy(
            schema,
            [np.zeros(0, dtype=c.dtype) for c in schema.columns],
            np.zeros(0, dtype=TIME_DTYPE),
            np.zeros(0, dtype=DIFF_DTYPE),
            capacity=capacity,
        )

    # -- host readback -----------------------------------------------------
    def to_numpy(self) -> dict:
        """Read valid rows back to host as a dict of numpy arrays.
        Duplicate column names are disambiguated with a positional suffix —
        use positional access (to_columns/to_rows) when names may repeat."""
        n = int(self.count)
        out = {}
        for i, (c, arr) in enumerate(zip(self.schema.columns, self.cols)):
            name = c.name if c.name not in out else f"{c.name}__{i}"
            out[name] = np.asarray(arr)[:n]
        out["__time__"] = np.asarray(self.time)[:n]
        out["__diff__"] = np.asarray(self.diff)[:n]
        return out

    def to_columns(self) -> list[np.ndarray]:
        """Valid rows of every column, positionally, + time and diff."""
        n = int(self.count)
        return [np.asarray(a)[:n] for a in self.cols] + [
            np.asarray(self.time)[:n],
            np.asarray(self.diff)[:n],
        ]

    def to_rows(self) -> list[tuple]:
        """Valid rows as python tuples (col..., time, diff) — for tests."""
        cols = self.to_columns()
        return [tuple(x.item() for x in row) for row in zip(*cols)]

    # -- shape management --------------------------------------------------
    def with_capacity(self, cap: int) -> "Batch":
        """Grow to a new capacity tier. Shrinking is forbidden: `count` is a
        traced value, so a shrink below it could silently drop valid rows."""
        if cap < self.capacity:
            raise ValueError(
                f"cannot shrink capacity {self.capacity} -> {cap}; "
                "rebuild via compact/consolidate instead"
            )

        def resize(a):
            if a is None:
                return None
            if a.shape[0] == cap:
                return a
            pad = jnp.zeros((cap - a.shape[0],), dtype=a.dtype)
            return jnp.concatenate([a, pad])

        return Batch(
            cols=tuple(resize(c) for c in self.cols),
            nulls=tuple(resize(n) for n in self.nulls),
            time=resize(self.time),
            diff=resize(self.diff),
            count=self.count,
            schema=self.schema,
        )

    def canonicalize_nulls(self) -> "Batch":
        """Make null-mask PRESENCE a function of the schema alone: nullable
        columns get a materialized (possibly all-False) mask, non-nullable
        columns get None. Needed wherever batches cross a fixed-structure
        boundary (lax.while_loop carries: pytree aux must match)."""
        nulls = []
        for c, nl, col in zip(self.cols, self.nulls, self.schema.columns):
            if col.nullable:
                nulls.append(
                    nl if nl is not None else jnp.zeros(c.shape[0], bool)
                )
            else:
                nulls.append(None)
        return self.replace(nulls=tuple(nulls))

    # replace() fields that can never invalidate a sortedness hint:
    # hints claim facts about row CONTENT order/uniqueness (and, for
    # "hash_sorted", times), so swapping cols/nulls/time voids them,
    # while diff (sign flips keep nonzero), count, schema rebrands
    # (same content, new names), and explicit hints do not. Dropping
    # by default here is what keeps the hint-consuming fast paths
    # (ops/consolidate.py, spine._arrange_for_run) sound without every
    # content-changing call site having to remember to launder.
    _HINT_SAFE_FIELDS = frozenset({"diff", "count", "schema", "hints"})

    def replace(self, **kw) -> "Batch":
        d = dict(
            cols=self.cols,
            nulls=self.nulls,
            time=self.time,
            diff=self.diff,
            count=self.count,
            schema=self.schema,
            hints=self.hints,
        )
        if not self._HINT_SAFE_FIELDS.issuperset(kw):
            d["hints"] = ()
        d.update(kw)
        return Batch(**d)
