"""Request journal: a jsonl write-ahead log of the serving engine's
accepted work, from which a REBUILT engine recovers in-flight requests
after a crash.

The scheduler's engine-failure cleanup (PR 3) keeps the *process*
serviceable, but a hard engine death (device loss, OOM kill of the
engine, an injected ``crash`` fault) still loses every in-flight
request: the caller holds error Results and nothing re-runs them. The
journal closes that gap with three record types through the standard
`observe.JsonlLogger` shape (new event types only — it is its own
file, never mixed into serve.jsonl):

- ``journal_submit``   at acceptance: everything needed to re-create
  the Request — id, prompt tokens, budget, eos, integer seed (explicit
  jax keys are not journalable — documented), the ORIGINAL relative
  deadline, and the trace_id, so a recovered request keeps its
  lifecycle identity across the crash boundary;
- ``journal_progress`` one batched record per written cycle: the
  cumulative emitted-token count of every emitting request, written
  every `progress_every` cycles (operator-facing progress accounting —
  recovery itself re-runs the request from scratch, which is what
  makes the recovered output bit-identical to an uncrashed run: the
  engine's serial-parity contract does the work, the journal only
  remembers WHAT to re-run — so the cadence is a cost knob, not a
  correctness one: one jsonl line per stride instead of one per slot
  per cycle keeps the armed clean path inside the <2% overhead bar);
- ``journal_finish``   at any terminal state, with the status.

A fourth, ``journal_migrate``, marks mid-decode slot migrations
(cluster drain, PR 18) — pure observability; the crash coverage of the
export→import gap rides entirely on the submit/finish pair (the source
finish, status ``"migrated"``, lands only after the peer's import).

Recovery = `pending_requests(path)`: every journaled submit without a
finish, in submit order. `LMServer.resubmit_pending` feeds them through
the normal admission path (chunked prefill + radix prefix cache
included), so a warm prefix cache carried across the rebuild serves
hits for the recovered prompts (gated by test).
"""

from __future__ import annotations

import json
import os
from pathlib import Path

import numpy as np

from idc_models_tpu.observe import JsonlLogger
from idc_models_tpu.serve.api import Request


class RequestJournal:
    """Append-only WAL the scheduler writes through. Accepts a path
    (opened line-buffered; `close()` fsyncs) — hand the SAME path to
    the rebuilt server so the recovery records append after the
    crashed run's."""

    def __init__(self, path: str | os.PathLike, *,
                 progress_every: int = 8):
        if progress_every < 1:
            raise ValueError(f"need progress_every >= 1, got "
                             f"{progress_every}")
        self.path = Path(path)
        self.progress_every = int(progress_every)
        self._progress_skips = 0
        self._logger = JsonlLogger(self.path)

    def record_submit(self, entry, *, deadline_s: float | None) -> None:
        """One accepted request, with everything `pending_requests`
        needs to rebuild it. `deadline_s` is the ORIGINAL relative
        deadline (the scheduler rewrites `entry.deadline` to absolute
        clock time, which is meaningless to a recovering process)."""
        seed = (int(entry.rng)
                if isinstance(entry.rng, (int, np.integer)) else None)
        # the tenant tag travels the WAL so a recovered request bills
        # the SAME tenant (quota + adapter) on the rebuilt server;
        # written only when tagged, so tenant-less journals stay
        # byte-identical to every file this format ever wrote
        tkw = ({"tenant": entry.tenant}
               if getattr(entry, "tenant", None) is not None else {})
        self._logger.log(
            event="journal_submit", id=entry.rid,
            prompt=[int(t) for t in
                    np.asarray(entry.prompt).reshape(-1)],
            max_new_tokens=int(entry.budget), eos_id=entry.eos_id,
            seed=seed, deadline_s=deadline_s, trace_id=entry.trace_id,
            **tkw)

    def record_progress(self, tokens_by_rid: dict) -> None:
        """One batched progress record for every request that emitted
        this cycle ({rid: cumulative tokens}), written every
        `progress_every` calls — the stride and the batching keep the
        journal's clean-path cost to a fraction of a jsonl line per
        cycle."""
        if not tokens_by_rid:
            return
        self._progress_skips += 1
        if self._progress_skips < self.progress_every:
            return
        self._progress_skips = 0
        self._logger.log(event="journal_progress",
                         tokens={str(r): int(n)
                                 for r, n in tokens_by_rid.items()})

    def record_finish(self, rid, status: str,
                      reason: str | None = None) -> None:
        self._logger.log(event="journal_finish", id=rid, status=status,
                         reason=reason)

    def record_migrate(self, rid, direction: str, *, peer: str) -> None:
        """One mid-decode migration boundary (serve/cluster drain):
        ``direction`` is ``"out"`` (this replica exported the slot) or
        ``"in"`` (this replica imported it); ``peer`` names the other
        replica. Observability only — recovery semantics ride on the
        submit/finish pair: the SOURCE journal's submit stays open until
        the peer's import lands (a crash inside the export→import gap
        replays the request here, bit-identically by the serial-parity
        contract), and only then does the source write the terminal
        ``journal_finish`` with status ``"migrated"``."""
        if direction not in ("out", "in"):
            raise ValueError(f"migration direction must be 'out' or "
                             f"'in', got {direction!r}")
        self._logger.log(event="journal_migrate", id=rid,
                         direction=direction, peer=peer)

    def close(self) -> None:
        self._logger.close()

    def __enter__(self) -> "RequestJournal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def load_journal(path) -> dict:
    """Parse a journal file into ``{"pending": [Request, ...],
    "finished": {id: status}, "progress": {id: tokens}}``. A request
    re-submitted by a previous recovery appears once (the LAST submit
    record wins); malformed lines raise — a torn WAL is a real error,
    not something to skip silently."""
    submits: dict = {}
    finished: dict = {}
    progress: dict = {}
    order: list = []
    for i, line in enumerate(Path(path).read_text().splitlines()):
        if not line.strip():
            continue
        try:
            rec = json.loads(line)
        except ValueError as e:
            raise ValueError(f"journal {path}: line {i + 1} is not "
                             f"JSON: {e}") from None
        ev = rec.get("event")
        if ev == "journal_submit":
            rid = rec["id"]
            if rid not in submits:
                order.append(rid)
            submits[rid] = rec
            # a re-submit after recovery reopens the request
            finished.pop(rid, None)
        elif ev == "journal_finish":
            # an ENGINE-failure death (status=error, reason=error — the
            # crash/abort cleanup path) is a recoverable in-flight loss,
            # exactly what the journal exists to replay; every other
            # terminal state (ok, deadline, shed, an exhausted
            # slot_fault) is the request's honest final answer
            if (rec.get("status") == "error"
                    and rec.get("reason") == "error"):
                finished.pop(rec["id"], None)
            else:
                finished[rec["id"]] = rec.get("status")
        elif ev == "journal_progress":
            for rid, n in rec.get("tokens", {}).items():
                progress[rid] = int(n)
    pending = []
    for rid in order:
        if rid in finished:
            continue
        rec = submits[rid]
        pending.append(Request(
            id=str(rid), prompt=tuple(rec["prompt"]),
            max_new_tokens=int(rec["max_new_tokens"]),
            eos_id=rec.get("eos_id"), seed=rec.get("seed"),
            deadline_s=rec.get("deadline_s"),
            trace_id=rec.get("trace_id"),
            tenant=rec.get("tenant")))
    return {"pending": pending, "finished": finished,
            "progress": progress}


def pending_requests(path) -> list[Request]:
    """The requests a crashed run accepted but never finished, in
    submit order — feed them back through `LMServer.submit` (or
    `LMServer.resubmit_pending`) on the rebuilt server."""
    return load_journal(path)["pending"]
