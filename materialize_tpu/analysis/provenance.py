"""Buffer-provenance analysis over the render-layer state trees.

ROADMAP item 4b was blocked on one unknown: can the replica's
``run_steps`` span train donate its carry while an ``IndexSource``
subscriber holds a live reference into the publisher's output spine?
Differential dataflow's economy (PAPERS.md) is built on shared
arrangements — many consumers reading one maintained spine — which is
exactly the aliasing pattern that makes ``donate_argnums`` unsafe to
sprinkle by hand: XLA is told the buffer is dead, but a Python-side
holder can still read it (or re-dispatch it as an operand) after the
donated program overwrote it in place.

Instead of guessing, this pass *computes* the aliasing. It walks every
registered root of a dataflow/view's device state —

- the span carry (operator states, output ``Spine``, err arrangement,
  device time scalar),
- rollback checkpoints and the deferred-span input log,
- ``MaintainedView`` multiversion history entries (device-resident
  per PERF_NOTES round 8),
- ``IndexSource`` subscriber base snapshots and pending delta queues,
- serving-cache retentions (peek program caches, transient-SELECT
  installs — these are whole dataflows, so their carries scan as
  ordinary roots),

— and assigns each device-array leaf a set of provenance classes plus
the list of holders (root, pytree path) that can reach it. Two holders
reaching one leaf IS the sharing graph; a leaf reachable from a carry
argnum *and* from any root outside that carry is what makes the argnum
un-donatable (analysis/donation.py turns this into the per-entry-point
verdict).

Identity is Python object identity of ``jax.Array`` leaves: the render
layer shares device state by sharing array objects (IndexSource's
device path hands over the very batches the publisher's step produced),
so ``id()`` equality is exactly "same buffer" for our sharing paths.
The pass is pure host work — no device transfers, no compiles.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax

# Provenance classes -------------------------------------------------------

PROV_CARRY = "span-carry-owned"
PROV_SHARED = "shared-across-dataflows"
PROV_HOST = "host-retained"
PROV_CACHE = "cache-retained"

# Roots whose class is PROV_CARRY, keyed by carry argnum name. The order
# mirrors the step program's donatable argnums (states, output, err, time)
# — the donation verdict is per entry in this tuple.
CARRY_PARTS = ("states", "output", "err_output", "time_dev")


def _is_device_leaf(x) -> bool:
    return isinstance(x, jax.Array)


def _path_str(path) -> str:
    try:
        s = jax.tree_util.keystr(path)
    except Exception:
        s = "".join(str(p) for p in path)
    return s or "."


@dataclass
class LeafRecord:
    """One device array's provenance: every (root, path) holder that
    can reach it, and the classes those holders imply."""

    leaf_id: int
    shape: tuple
    dtype: str
    nbytes: int
    classes: set = field(default_factory=set)
    holders: list = field(default_factory=list)  # [(root, path_str)]

    def chain(self) -> str:
        """Human-readable provenance chain (who holds this buffer)."""
        return " ; ".join(f"{root}{path}" for root, path in self.holders)


@dataclass
class ProvenanceReport:
    """The scan result over a set of named dataflows/views."""

    leaves: dict = field(default_factory=dict)  # id -> LeafRecord
    # producer dataflow -> {consumer root names aliasing its carry}
    sharing: dict = field(default_factory=dict)
    # dataflow -> carry part -> [leaf ids]
    carries: dict = field(default_factory=dict)

    # -- scan helpers --------------------------------------------------------
    def add_root(self, root: str, cls: str, tree) -> list:
        """Record every device leaf under ``tree`` as reachable from
        ``root`` with class ``cls``; returns the leaf ids."""
        ids = []
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in leaves:
            if not _is_device_leaf(leaf):
                continue
            rec = self.leaves.get(id(leaf))
            if rec is None:
                rec = LeafRecord(
                    id(leaf),
                    tuple(leaf.shape),
                    str(leaf.dtype),
                    int(leaf.size * leaf.dtype.itemsize),
                )
                self.leaves[id(leaf)] = rec
            rec.classes.add(cls)
            rec.holders.append((root, _path_str(path)))
            ids.append(id(leaf))
        return ids

    # -- queries -------------------------------------------------------------
    def class_census(self) -> dict:
        out: dict = {}
        for rec in self.leaves.values():
            for c in rec.classes:
                out[c] = out.get(c, 0) + 1
        return out

    def shared_leaves(self, df_name: str, part: str) -> list:
        """Leaf records under ``df_name``'s carry ``part`` that some
        holder OUTSIDE that carry also reaches (the un-donatable set)."""
        carry_root = f"{df_name}/carry"
        out = []
        for lid in self.carries.get(df_name, {}).get(part, ()):
            rec = self.leaves[lid]
            if any(
                not root.startswith(carry_root)
                for root, _ in rec.holders
            ):
                out.append(rec)
        return out


def _carry_tree(df) -> dict:
    """The step program's donatable carry, keyed by argnum name."""
    return {
        "states": tuple(df.states),
        "output": df.output,
        "err_output": df.err_output,
        "time_dev": getattr(df, "_time_dev", None),
    }


def scan_dataflow(report: ProvenanceReport, name: str, df) -> None:
    """Scan one rendered dataflow's device roots into ``report``."""
    carry = _carry_tree(df)
    parts: dict = {}
    for part in CARRY_PARTS:
        parts[part] = report.add_root(
            f"{name}/carry/{part}", PROV_CARRY, carry[part]
        )
    report.carries[name] = parts
    # Rollback retention: the deferred-window checkpoint and input log.
    # A DONATED window clones the checkpoint to fresh buffers — if the
    # scan ever finds a checkpoint leaf aliasing the carry while
    # donation is on, the clone contract broke.
    ck = getattr(df, "_defer_ck", None)
    if ck is not None:
        report.add_root(f"{name}/defer_ck", PROV_HOST, ck)
    for i, (packed, env) in enumerate(getattr(df, "_defer_log", ())):
        report.add_root(f"{name}/defer_log[{i}]", PROV_HOST, packed)
    # Serving caches (peek jit cache, span hints) retain only CODE and
    # host ints — never device operands — so there is nothing to scan;
    # PROV_CACHE exists for future retentions that do hold arrays
    # (record them here with add_root(..., PROV_CACHE, tree)).


def scan_view(report: ProvenanceReport, name: str, view) -> None:
    """Scan one MaintainedView: its dataflow's roots plus the
    view-level retentions (multiversion history, subscriber handoffs)."""
    scan_dataflow(report, name, view.df)
    for i, (t, upd) in enumerate(getattr(view, "_history", ())):
        if not isinstance(upd, tuple):  # device-resident entry
            report.add_root(
                f"{name}/history[t={t}]", PROV_HOST, upd
            )
    # A sinked span validated and not yet written: its deltas stay on
    # the device beneath the NEXT span's (donated) dispatch, as the
    # history's do. Outputs of their own steps, never carry leaves.
    validated = getattr(view, "_validated_span", None)
    for t, upd in validated[1] if validated else ():
        report.add_root(f"{name}/validated[t={t}]", PROV_HOST, upd)
    for si, sub in enumerate(getattr(view, "_subscribers", ())):
        if not getattr(sub, "_device", False):
            continue  # host-path subscribers copy through numpy
        sroot = f"{name}/subscriber[{si}]"
        base = getattr(sub, "base_batch", None)
        base_ids = (
            report.add_root(f"{sroot}/base", PROV_SHARED, base)
            if base is not None
            else []
        )
        pend_ids = []
        for t, upd in getattr(sub, "_pending", ()):
            pend_ids.extend(
                report.add_root(
                    f"{sroot}/pending[t={t}]", PROV_SHARED, upd
                )
            )
        # Sharing graph: does this subscriber alias the publisher's
        # carry? (base snapshots alias the output spine unless the
        # subscribe-time clone ran; pending deltas are span outputs
        # and should never alias.)
        carry_ids = set()
        for ids in report.carries.get(name, {}).values():
            carry_ids.update(ids)
        if carry_ids.intersection(base_ids + pend_ids):
            report.sharing.setdefault(name, set()).add(sroot)


def scan_replica(views: dict) -> ProvenanceReport:
    """Scan every installed view of a replica (name -> MaintainedView):
    cross-dataflow aliasing (one view's IndexSource holding another
    view's spine) falls out of the shared leaf table."""
    report = ProvenanceReport()
    for name, view in sorted(views.items()):
        scan_view(report, name, view)
    # Cross-dataflow sharing: a leaf under view A's carry that any
    # root of a DIFFERENT view reaches.
    for name in views:
        carry_ids = set()
        for ids in report.carries.get(name, {}).values():
            carry_ids.update(ids)
        for lid in carry_ids:
            for root, _ in report.leaves[lid].holders:
                owner = root.split("/", 1)[0]
                if owner != name:
                    report.sharing.setdefault(name, set()).add(root)
                    report.leaves[lid].classes.add(PROV_SHARED)
    return report
