"""Crash-recovery write-ahead journal for the serving engine.

The PR 12 graceful drain published a requeue journal — but only as a
telemetry event at drain time, so it existed exactly when the process died
*politely*.  A SIGKILL (OOM killer, node loss, ``kill -9``) lost every
in-flight request.  This module promotes that journal to a **write-ahead
journal on disk**: every admission and every terminal transition (complete /
deadline-shed / quarantine) rewrites one JSON file via the checkpoint
manifest's write-temp + ``os.replace`` pattern, so the file on disk is
always a complete, parseable snapshot — a kill mid-write leaves the
*previous* complete journal, never a torn one.

Recovery contract (:meth:`ServingEngine.recover_from_journal`): a successor
engine resubmits every journaled request with no terminal record as
``prompt + emitted`` with ``max_new = remaining``.  Greedy decode is
deterministic and the re-prefill path is bit-exact (the PR 12 drain oracle),
so the successor finishes every non-shed request **token-identically** to an
uninterrupted run — whether the predecessor died by SIGTERM (drain persisted
its emitted-token progress) or SIGKILL (the request replays from the
prompt; same tokens, more compute).

What is journaled when:

- **admission** (``record_admit``) — prompt, budget, tag, deadlines.  The
  write happens before ``submit`` returns the id, so an acknowledged
  request is always recoverable.
- **terminal** (``record_done``) — status ``ok`` / ``deadline_expired`` /
  ``quarantined``.  Terminal requests are never replayed (a quarantined
  request poisoned a decode once; replaying it would poison the successor).
- **drain** (``record_progress``) — emitted tokens per still-pending
  request, so a SIGTERM'd engine's successor resumes mid-request instead
  of re-decoding from the prompt.

Emitted tokens are deliberately NOT journaled per decode tick: that would
put a disk write on the hot path, and recovery does not need it for
token-identity — only for avoiding recompute, which the drain path covers.

**Tier residency** (``record_tier``): when the KV host tier migrates a
preempted request's blocks to host DRAM (and again when they promote back),
the request's entry gains a ``tier`` record — residency (``"host"`` /
``"device"``), demoted row count, and emitted-token progress at migration
time.  Host DRAM dies with the process, so a successor can never reload the
demoted bytes; the record exists so recovery can rebuild *either way* (the
emitted progress rides along exactly like a drain's ``record_progress``) and
so post-mortem forensics can see which requests were host-resident at the
kill.  Same schema version — readers ignore keys they do not use.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Dict, List, Optional

from ..telemetry import annotate

__all__ = ["ServingJournal", "JournalError", "JOURNAL_VERSION"]

JOURNAL_VERSION = 1


class JournalError(RuntimeError):
    """The journal file is missing, unreadable, or from a newer schema."""


def _fsync_enabled() -> bool:
    return os.environ.get(
        "ACCELERATE_TPU_CHECKPOINT_FSYNC", "1"
    ).strip().lower() not in ("0", "false", "no", "off")


class ServingJournal:
    """One engine's write-ahead journal: an in-memory state mirrored to
    ``path`` atomically on every mutation.

    The file is written lazily — a fresh engine pointed at a dead
    predecessor's journal can still :meth:`load` it for recovery before the
    first admission overwrites it."""

    def __init__(self, path: str):
        self.path = path
        self._requests: Dict[str, dict] = {}
        self._done: Dict[str, str] = {}
        self._flushed = False
        self._deferred = False

    @property
    def flushed(self) -> bool:
        """Whether this journal has written ``path`` at least once (after
        which a predecessor's journal at the same path is gone)."""
        return self._flushed

    @contextlib.contextmanager
    def deferred(self):
        """Batch mutations into ONE atomic flush at context exit.  Recovery
        needs this: resubmitting N pending requests one-by-one would
        overwrite the predecessor's journal after the FIRST resubmit — a
        SIGKILL mid-recovery would then lose the other N-1 on disk.  With
        the batch, the predecessor's file survives intact until every
        pending request is re-journaled in a single ``os.replace``."""
        self._deferred = True
        try:
            yield self
        finally:
            self._deferred = False
            self._flush()

    # -- mutation (each call lands on disk before returning) -----------------

    def record_admit(self, req) -> None:
        self._requests[str(req.id)] = {
            "prompt": list(req.prompt),
            "max_new_tokens": int(req.max_new_tokens),
            "tag": req.tag,
            "ttft_deadline_ms": req.ttft_deadline_ms,
            "deadline_ms": req.deadline_ms,
            "denoise_steps": req.denoise_steps,
            "confidence_threshold": req.confidence_threshold,
            "emitted": [],
            # Wall-clock admission anchor: the tracer's cross-life stitcher
            # dates the victim's life from it even when the victim never
            # flushed a trace line (monotonic clocks die with the process).
            # Same schema version — readers ignore keys they do not use.
            "arrival_wall": time.time(),
        }
        self._flush()

    def record_done(self, rid: int, status: str) -> None:
        self._done[str(rid)] = status
        self._flush()

    def record_tier(self, req, residency: str) -> None:
        """Persist a request's KV tier residency transition (``"host"`` on
        demotion, ``"device"`` on promotion or fallback re-prefill), plus its
        emitted progress at that moment — so a successor resumes a killed
        host-resident request from its last migration point instead of the
        bare prompt, exactly as if a drain had recorded progress."""
        entry = self._requests.get(str(req.id))
        if entry is None:
            return
        entry["tier"] = {
            "residency": residency,
            "demoted_rows": int(req.demoted_rows),
            "demoted_blocks": len(req.demoted_blocks or ()),
            "migrations": int(req.migrations),
        }
        entry["emitted"] = list(req.emitted)
        self._flush()

    def record_progress(self, reqs) -> None:
        """Persist emitted-token progress for still-pending requests (the
        drain path calls this once with the whole requeue set)."""
        for req in reqs:
            entry = self._requests.get(str(req.id))
            if entry is not None:
                entry["emitted"] = list(req.emitted)
        self._flush()

    def _flush(self) -> None:
        if self._deferred:
            return
        state = {
            "version": JOURNAL_VERSION,
            "requests": self._requests,
            "done": self._done,
        }
        tmp = f"{self.path}.tmp"
        parent = os.path.dirname(os.path.abspath(self.path))
        with annotate("serving.journal.append", requests=len(self._requests)):
            os.makedirs(parent, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(state, f)
                f.flush()
                if _fsync_enabled():
                    try:
                        os.fsync(f.fileno())
                    except OSError:
                        pass
            os.replace(tmp, self.path)
        self._flushed = True

    # -- recovery ------------------------------------------------------------

    @staticmethod
    def load(path: str) -> dict:
        """Parse a journal file; raises :class:`JournalError` when it is
        missing, unparseable, or from a newer schema (an older engine must
        not silently drop fields it does not understand)."""
        try:
            with open(path) as f:
                state = json.load(f)
        except FileNotFoundError:
            raise JournalError(f"no journal at {path!r}") from None
        except (OSError, json.JSONDecodeError) as e:
            raise JournalError(f"unreadable journal at {path!r}: {e}") from e
        version = state.get("version")
        if not isinstance(version, int) or version > JOURNAL_VERSION:
            raise JournalError(
                f"journal {path!r} has schema version {version!r}; this "
                f"engine understands <= {JOURNAL_VERSION}"
            )
        if not isinstance(state.get("requests"), dict) or not isinstance(
            state.get("done"), dict
        ):
            raise JournalError(f"journal {path!r} is structurally invalid")
        return state

    @staticmethod
    def pending(state: dict) -> List[dict]:
        """The journaled requests with no terminal record, oldest admission
        first (ids are monotonic), each with its original id under
        ``"id"``."""
        done = state["done"]
        out = []
        for rid in sorted(state["requests"], key=int):
            if rid not in done:
                rec = dict(state["requests"][rid])
                rec["id"] = int(rid)
                out.append(rec)
        return out
