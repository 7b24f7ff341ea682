"""Persistent cross-process AOT program bank (ISSUE 16 tentpole a).

A directory of serialized compiled executables, keyed by the compile
ledger's ``(kind, dataflow fingerprint, tier vector)`` identity. The
payload is ``jax.experimental.serialize_executable.serialize`` output —
the PJRT *executable*, not just StableHLO — so a bank hit pays a
deserialize (tens of milliseconds) instead of an XLA compile (seconds
to minutes: ~26s index step, 112s 4-operand sort on real hardware,
PERF_NOTES facts 6).

Entries are environment-stamped (jax/jaxlib versions, backend
platform, device count): a stale-jaxlib or cross-platform entry is
skipped, never loaded — an executable serialized by a different
runtime is at best unloadable and at worst wrong. A truncated or
corrupt entry is unlinked best-effort and reported as a miss; the
caller falls back to a clean compile, so a damaged bank can degrade
recovery time but never correctness. Stores are load-verified before
export (see ``ProgramBank.store``), so a published entry is one this
runtime demonstrably deserializes.

Writes are atomic (tmp + rename into place) so concurrent processes
(replica subprocesses sharing the blob dir with environmentd) never
observe half-written entries. The bank lives under the deployment's
blob directory (``<data-dir>/blob/program_bank``) so
``environmentd --recover`` finds a warm bank exactly where the durable
state already is.
"""

from __future__ import annotations

import os
import pickle
import threading
import time as _time

# Bump when the entry layout changes: old-format entries are skipped.
BANK_FORMAT = 1

# Environment variable fallback: subprocess replicas inherit the bank
# location without threading a flag through every entry point.
BANK_ENV_VAR = "MZ_PROGRAM_BANK"


def _entry_filename(kind: str, fingerprint: str, tier: str) -> str:
    # tier vectors are "<hex>:<bytes>"; keep filenames shell-safe.
    safe = "".join(
        c if (c.isalnum() or c in "._-") else "_" for c in tier
    )
    return f"{kind}__{fingerprint}__{safe}.aot"


def _env_stamp() -> dict:
    import jax
    import jaxlib

    return {
        "format": BANK_FORMAT,
        "jax": jax.__version__,
        "jaxlib": jaxlib.version.__version__,
        "platform": jax.default_backend(),
        "devices": jax.device_count(),
    }


class ProgramBank:
    """One bank directory. Thread-safe; cheap to construct."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        # Tracked (ISSUE 17): the bank store lock is taken from every
        # jit site AND the async compile worker — it must stay a leaf
        # in the observed lock-order graph.
        from ..utils.lockcheck import tracked_lock

        self._lock = tracked_lock("compile.bank")
        self._stamp: dict | None = None
        # Counters for mz_program_bank / the recovery report.
        self.stats = {
            "hits": 0,       # entries deserialized and served
            "misses": 0,     # lookups that found no usable entry
            "stores": 0,     # entries written back
            "errors": 0,     # corrupt/skewed/unserializable entries
            "seconds_recovered": 0.0,  # compile wall the hits skipped
        }

    # -- key paths ---------------------------------------------------------
    def path_for(self, kind: str, fingerprint: str, tier: str) -> str:
        return os.path.join(
            self.root, _entry_filename(kind, fingerprint, tier)
        )

    def has(self, kind: str, fingerprint: str, tier: str) -> bool:
        """Existence only — no load, no environment check. Used by the
        ledger's ``_seen`` eviction fix: a key the bank holds was
        compiled SOMEWHERE, so its recompile is never a cold miss."""
        return os.path.exists(self.path_for(kind, fingerprint, tier))

    def _environment(self) -> dict:
        if self._stamp is None:
            self._stamp = _env_stamp()
        return self._stamp

    # -- lookup / store ----------------------------------------------------
    def lookup(self, kind: str, fingerprint: str, tier: str):
        """Load an entry's executable. Returns ``(compiled, meta)`` or
        ``None``. Never raises: corruption, version skew, and
        deserialize failures all resolve to a miss (the caller
        compiles cleanly); a provably corrupt file is unlinked so the
        next process doesn't re-pay the failed load."""
        path = self.path_for(kind, fingerprint, tier)
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
        except FileNotFoundError:
            self._count("misses")
            return None
        except Exception:
            # Truncated/corrupt pickle: drop the entry, fall back.
            self._damaged(path)
            return None
        meta = entry.get("meta") if isinstance(entry, dict) else None
        if meta is None or "payload" not in entry:
            self._damaged(path)
            return None
        env = self._environment()
        for k in ("format", "jax", "jaxlib", "platform", "devices"):
            if meta.get(k) != env[k]:
                # Version/platform skew: not corruption — another
                # deployment (or a future upgrade rollback) may still
                # want it. Skip, don't unlink.
                self._count("misses", "errors")
                return None
        try:
            from jax.experimental import serialize_executable

            compiled = serialize_executable.deserialize_and_load(
                *entry["payload"]
            )
        except Exception:
            self._damaged(path)
            return None
        self._count("hits")
        with self._lock:
            self.stats["seconds_recovered"] += float(
                meta.get("seconds", 0.0)
            )
        return compiled, meta

    def store(
        self,
        kind: str,
        fingerprint: str,
        tier: str,
        compiled,
        seconds: float = 0.0,
        name: str = "",
    ) -> bool:
        """Serialize an executable into the bank (atomic write).
        ``seconds`` is the compile wall this entry cost — what a
        future hit recovers (the recovery report's
        ``compile_seconds_recovered``). Returns False (and counts an
        error) if the program isn't serializable; the caller keeps
        its in-process compiled program either way."""
        try:
            from jax.experimental import serialize_executable

            payload = serialize_executable.serialize(compiled)
            # Verify the payload actually loads BEFORE exporting it:
            # some runtimes (observed on jaxlib CPU) serialize a
            # module whose compile was not the first in-process
            # instance into a payload that fails deserialization with
            # "Symbols not found". A bank must never publish an entry
            # a fresh process cannot serve — the ~tens-of-ms load here
            # guards the seconds-to-minutes compile it replaces.
            serialize_executable.deserialize_and_load(*payload)
            entry = {
                "meta": {
                    **self._environment(),
                    "kind": kind,
                    "fingerprint": fingerprint,
                    "tier": tier,
                    "name": name,
                    "seconds": float(seconds),
                    "stored_at": _time.time(),
                },
                "payload": payload,
            }
            blob = pickle.dumps(
                entry, protocol=pickle.HIGHEST_PROTOCOL
            )
            path = self.path_for(kind, fingerprint, tier)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except Exception:
            self._count("errors")
            return False
        self._count("stores")
        return True

    def _count(self, *keys: str) -> None:
        """Bump counters in ``stats`` AND in this process's /metrics
        registry (``mz_program_bank_<key>_total``): a replica's
        families ship to the controller, so its bank's behaviour —
        above all ``errors`` — is visible from the server
        (mz_metrics), not only inside the replica process."""
        from ..utils.metrics import REGISTRY

        with self._lock:
            for k in keys:
                self.stats[k] += 1
        for k in keys:
            REGISTRY.get_or_create(
                "counter", f"mz_program_bank_{k}_total",
                f"program bank {k} in this process",
            ).inc()

    def note_error(self) -> None:
        """A banked program the caller had to give up on (the AOT
        compile failed, or the runtime refused the loaded executable)
        and route through the plain jit instead: counted, so the
        fallback is seen."""
        self._count("errors")

    def _damaged(self, path: str) -> None:
        self._count("misses", "errors")
        try:
            os.unlink(path)
        except OSError:
            pass

    # -- introspection (mz_program_bank) -----------------------------------
    def entries(self) -> list[dict]:
        """Per-entry metadata without loading executables: parse the
        key back out of the filename, stat for size/mtime. Unreadable
        names are skipped (a foreign file in the dir is not an
        error)."""
        out = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return out
        for fn in names:
            if not fn.endswith(".aot"):
                continue
            parts = fn[: -len(".aot")].split("__")
            if len(parts) != 3:
                continue
            kind, fingerprint, tier = parts
            try:
                st = os.stat(os.path.join(self.root, fn))
            except OSError:
                continue
            out.append(
                {
                    "kind": kind,
                    "fingerprint": fingerprint,
                    "tier": tier,
                    "bytes": int(st.st_size),
                    "stored_at": float(st.st_mtime),
                }
            )
        return out

    def snapshot(self) -> dict:
        """Counters + entry census: the recovery report's and
        ``mz_program_bank``'s surface."""
        with self._lock:
            stats = dict(self.stats)
        ents = self.entries()
        stats["entries"] = len(ents)
        stats["bytes"] = sum(e["bytes"] for e in ents)
        stats["seconds_recovered"] = round(
            stats["seconds_recovered"], 3
        )
        return stats


# -- process-global bank -----------------------------------------------------
# `BANK` is read on the ledger_jit dispatch path: module attribute, no
# function call, None when the bank is off (the default — bank-off
# dispatch stays byte-identical to the pre-bank hot path).
BANK: ProgramBank | None = None
_resolved = False


def configure_bank(path: str | None) -> ProgramBank | None:
    """Point this process at a bank directory (None disables). Called
    by environmentd/replica boot and tests."""
    global BANK, _resolved
    _resolved = True
    BANK = ProgramBank(path) if path else None
    return BANK


def get_bank() -> ProgramBank | None:
    """The configured bank, resolving the MZ_PROGRAM_BANK environment
    variable once on first use (subprocess replicas inherit it)."""
    global BANK, _resolved
    if not _resolved:
        _resolved = True
        path = os.environ.get(BANK_ENV_VAR)
        if path:
            BANK = ProgramBank(path)
    return BANK
