"""Durable state + restart: the standalone analog of "the Kubernetes API
is the durable store" (SURVEY.md §5 checkpoint/resume).

The reference persists every state transition in object status via SSA
patches (pkg/workload/patching) and rebuilds its caches from informers
on restart; nothing else is checkpointed. Here the same contract is an
append-only JSONL journal of applied objects:

  * every engine object creation and every workload status transition
    appends an ``apply`` record (the SSA-patch analog — last write per
    key wins);
  * ``rebuild_engine`` cold-starts an engine from the journal: objects
    are re-created in order, then each workload's last persisted state
    is restored through Engine.restore_workload — admitted workloads
    re-assume their cache usage, pending ones re-enter the queues with
    their requeue backoff intact (the informer-rebuild path,
    e.g. scheduler.go:554-557 in-flight recovery note);
  * ``compact`` rewrites the log to one record per live key.

Crash consistency: records are flushed per append (fsync optional), and
the engine calls ``sync()`` (flush+fsync) on every non-idle cycle
boundary so an applied admission can never be lost to a crash between
cycles; a truncated or corrupt final line is trimmed on reattach and
ignored on replay, mirroring at-least-once status patching, while
corruption anywhere else raises (silent record loss is worse than a
failed restart).

Segment rotation (bounded-time recovery): with ``rotate_bytes`` /
``rotate_records`` set, ``sync()`` seals the active file as
``<path>.seg<NNNNNN>`` once it crosses a threshold and reopens a fresh
active file whose FIRST line is a ``meta`` control record carrying the
ordinal the new active will take when sealed and the journal's
*lineage*. The logical journal is the concatenation of the
lineage-matching sealed segments (ordinal order) and the active file;
``replay()`` walks exactly that. Compaction bumps the lineage, which
atomically invalidates every sealed segment (and every checkpoint —
store/checkpoint.py pins the lineage it snapshotted) left behind by a
crash mid-cleanup: a stale segment is excluded by its old lineage, not
by a cleanup step that might never have run. Sealed segments older
than the oldest live checkpoint are deleted by ``retain_segments``;
``replay_from`` yields only the records past a checkpoint's
(lineage, segment, offset) position — the O(delta) recovery path.
"""

from __future__ import annotations

import json
import os
from typing import Iterator, Optional

from kueue_tpu.api.serde import from_jsonable, to_jsonable

# Crash hook for fault injection (replay/faults.py sigkill@compaction):
# called with "rotate" / "compact" at the nastiest point of the
# maintenance operation — after the rename/replace, before cleanup and
# reopen — so recovery is proven against a half-finished maintenance
# pass, not just a half-written record.
MAINTENANCE_CRASH_HOOK = None

_SEG_WIDTH = 6
_META_KIND = "__journal__"


def _segment_path(path: str, ordinal: int) -> str:
    return f"{path}.seg{ordinal:0{_SEG_WIDTH}d}"


def _sealed_segments(path: str) -> list:
    """Sorted [(ordinal, segpath)] of the sealed segment files."""
    base = os.path.basename(path) + ".seg"
    d = os.path.dirname(path) or "."
    out = []
    try:
        names = os.listdir(d)
    except FileNotFoundError:
        return out
    for name in names:
        if name.startswith(base) and name[len(base):].isdigit():
            out.append((int(name[len(base):]), os.path.join(d, name)))
    out.sort()
    return out


def _file_meta(path: str) -> Optional[dict]:
    """The ``meta`` control record on a journal file's FIRST line, or
    None (genesis files predate rotation and carry none)."""
    try:
        with open(path, "rb") as fh:
            line = fh.readline(1 << 16)
    except FileNotFoundError:
        return None
    if not line.endswith(b"\n"):
        return None
    try:
        rec = json.loads(line)
    except json.JSONDecodeError:
        return None
    return rec if rec.get("op") == "meta" else None


class JournalCorruption(Exception):
    """A record that is neither the torn final line nor parseable:
    replaying past it would silently drop every later record."""


class JournalFenced(Exception):
    """A write was refused by the journal's fence predicate: the holder
    is no longer the leader (HA fencing — a deposed leader's in-flight
    writes must die here rather than interleave with the new leader's;
    see kueue_tpu/ha/replica.py)."""


class JournalConflict(Exception):
    """Optimistic-concurrency failure: the object was modified by another
    writer since the caller read it (the SSA patch-conflict analog,
    pkg/workload/patching/patching.go:53-59 — the reference retries
    after re-reading)."""

    def __init__(self, kind: str, key: str, expected: int, found: int):
        super().__init__(
            f"conflict on {kind}/{key}: expected generation {expected},"
            f" journal has {found}")
        self.kind = kind
        self.key = key
        self.expected = expected
        self.found = found


class JournalDegraded(Exception):
    """A write was refused by the disk budget (store/diskguard.py):
    the filesystem is at or below min_free_bytes and the journal is in
    read-only degraded mode. Distinct from ENOSPC-the-OSError on
    purpose — the caller (serving front door, drive loop) sheds and
    parks instead of crashing, and the budget re-arms itself when
    space returns."""


class Journal:
    """Append-only JSONL journal with per-key GENERATION stamps.

    Multi-writer safety (a second replica, the out-of-process CLI): every
    ``apply`` first refreshes from the shared file — appends made by
    other writers since our last read are folded into the per-key
    generation table — and then appends with generation last+1. A caller
    that read an object at generation G can pass
    ``expected_generation=G``; if another writer advanced the key past G
    in the meantime the apply raises JournalConflict instead of silently
    clobbering (exactly the SSA conflict-retry contract). Appends use
    O_APPEND single-write records, so concurrent writers interleave at
    record granularity."""

    def __init__(self, path: str, fsync: bool = False,
                 rotate_bytes: Optional[int] = None,
                 rotate_records: Optional[int] = None,
                 min_free_bytes: int = 0, metrics=None):
        from kueue_tpu.store.diskguard import DiskBudget

        self.path = path
        self.fsync = fsync
        # Segment rotation thresholds (None/0 = rotation off — the
        # original single-file behavior, byte for byte).
        self.rotate_bytes = int(rotate_bytes or 0)
        self.rotate_records = int(rotate_records or 0)
        # Disk budget (0 = guard off): preflight every append against
        # free space and degrade to read-only instead of crashing on a
        # filling disk. See store/diskguard.py.
        self.budget = DiskBudget(path, min_free_bytes, metrics=metrics)
        # Optional fence predicate (HA): evaluated INSIDE the append
        # flock; returning False raises JournalFenced instead of
        # writing. None (the default) means unfenced.
        self.fence = None
        self._fh = open(path, "a", encoding="utf-8")
        # Appends since the last sync(): the engine calls sync() on
        # cycle boundaries (write+flush+fsync), so a crash between
        # cycles never loses an applied admission and per-append fsync
        # stays optional for the hot path.
        self._dirty = False
        self._locked_repair()
        # Per-(kind, key) generation table + how far we've read the
        # active file, which inode that offset belongs to, and how many
        # complete LINES of the active file it covers (the checkpoint
        # position coordinate).
        self._generations: dict[tuple, int] = {}
        self._read_offset = 0
        self._read_ino = os.fstat(self._fh.fileno()).st_ino
        self._active_lines = 0
        # Generations recovered from a checkpoint (seed_generations):
        # segments the retention pass deleted may hold a key's only
        # write, so the file scan alone would under-count. Merged as a
        # floor on every rescan.
        self._seed_gens: dict[tuple, int] = {}
        self.refresh()

    # -- segment topology --

    def sealed_segments(self) -> list:
        """Sorted [(ordinal, path)] of sealed segments in the CURRENT
        lineage (stale-lineage leftovers of a crashed compaction are
        excluded — their content is superseded by the compacted file)."""
        lineage = self.lineage
        out = []
        for ordinal, seg in _sealed_segments(self.path):
            meta = _file_meta(seg)
            if int((meta or {}).get("lineage", 0)) == lineage:
                out.append((ordinal, seg))
        return out

    @property
    def lineage(self) -> int:
        """Compaction era. Bumped by compact(); sealed segments and
        checkpoints from older lineages are dead on arrival."""
        meta = _file_meta(self.path)
        if meta is not None:
            return int(meta.get("lineage", 0))
        segs = _sealed_segments(self.path)
        if segs:
            m = _file_meta(segs[-1][1])
            if m is not None:
                return int(m.get("lineage", 0))
        return 0

    def active_ordinal(self) -> int:
        """The ordinal the active file will take when sealed."""
        meta = _file_meta(self.path)
        if meta is not None and "seg" in meta:
            return int(meta["seg"])
        segs = _sealed_segments(self.path)
        return (segs[-1][0] + 1) if segs else 0

    def position(self) -> dict:
        """Where the journal ends right now, as a recovery coordinate:
        ``{"lineage", "segment", "offset"}`` — offset counts complete
        LINES of the active file (meta line included). A checkpoint
        stores this; ``replay_from`` resumes here."""
        self.refresh()
        return {"lineage": self.lineage,
                "segment": self.active_ordinal(),
                "offset": self._active_lines}

    @property
    def degraded(self) -> bool:
        """True while the disk budget holds the journal read-only.
        The serving front door checks this to shed new submissions
        (503 disk-pressure) and the drive loop checks it to park
        scheduling until ``rearm_probe`` succeeds."""
        return self.budget.degraded

    def rearm_probe(self) -> bool:
        """Re-check free space and re-arm if recovered. Returns True
        when the journal is writable (armed) after the probe."""
        return self.budget.rearm_probe()

    def writable(self) -> bool:
        """Cycle-boundary gate for drive loops: True when appends may
        proceed. Unlike ``degraded`` (a passive flag), this actively
        probes — an armed budget over a newly-full filesystem degrades
        HERE, before the engine schedules work it cannot journal, and
        a degraded budget re-arms the moment space recovers."""
        if not self.budget.enabled:
            return True
        if self.budget.degraded:
            return self.budget.rearm_probe()
        return self.budget.preflight(256)

    def seed_generations(self, gens: dict) -> None:
        """Floor the generation table with checkpoint-recovered stamps
        (``{(kind, key): gen}``): retention may have deleted the segment
        holding a key's latest write, and a fresh handle must not
        restart that key at generation 1."""
        for k, g in gens.items():
            g = int(g)
            self._seed_gens[k] = max(self._seed_gens.get(k, 0), g)
            if g > self._generations.get(k, 0):
                self._generations[k] = g

    def refresh(self) -> int:
        """Fold records appended by OTHER writers (or our own) since the
        last read into the generation table. Returns the number of new
        records seen."""
        n = 0
        try:
            with open(self.path, "rb") as fh:
                st = os.fstat(fh.fileno())
                if (st.st_ino != self._read_ino
                        or st.st_size < self._read_offset):
                    # The active file was swapped (rotation/compaction
                    # by another handle) or shrank (torn-tail repair):
                    # rescan the whole segment chain from scratch.
                    self._rescan_base()
                    self._read_ino = st.st_ino
                fh.seek(self._read_offset)
                data = fh.read()
        except FileNotFoundError:
            return 0
        if not data:
            return 0
        # Only complete lines advance the offset (another writer may be
        # mid-append).
        end = data.rfind(b"\n")
        if end < 0:
            return 0
        for line in data[:end].split(b"\n"):
            self._active_lines += 1
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("op") == "meta":
                continue
            key = (rec.get("kind"), _key_of(rec))
            self._generations[key] = int(rec.get("gen", 0)) or \
                self._generations.get(key, 0) + 1
            n += 1
        self._read_offset += end + 1
        return n

    def _rescan_base(self) -> None:
        """Reset the incremental-read state and fold every sealed
        segment's generations back in (the active file is re-read by the
        refresh() that called us). Checkpoint-seeded floors survive."""
        self._read_offset = 0
        self._active_lines = 0
        self._generations.clear()
        for _ordinal, seg in self.sealed_segments():
            try:
                with open(seg, "rb") as fh:
                    data = fh.read()
            except FileNotFoundError:
                continue
            end = data.rfind(b"\n")
            for line in data[:end].split(b"\n") if end >= 0 else ():
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("op") == "meta":
                    continue
                key = (rec.get("kind"), _key_of(rec))
                self._generations[key] = int(rec.get("gen", 0)) or \
                    self._generations.get(key, 0) + 1
        for k, g in self._seed_gens.items():
            if g > self._generations.get(k, 0):
                self._generations[k] = g

    def generation_of(self, kind: str, key: str) -> int:
        """The last persisted generation for a key (0 = never written).
        Callers doing read-modify-write pass this back as
        ``expected_generation``."""
        self.refresh()
        return self._generations.get((kind, key), 0)

    def _repair_torn_tail(self) -> None:
        """Trim a truncated or corrupt final line (crash mid-write) so
        post-restart appends start on a clean line — otherwise the first
        new record would concatenate onto the fragment and everything
        after it would be unreadable on the next replay. Covers both
        crash artifacts: a newline-less fragment AND a newline-terminated
        final line that doesn't parse (a torn write that happened to end
        on the terminator byte). Repair never removes more than the
        single damaged record."""
        if not os.path.exists(self.path):
            return
        with open(self.path, "rb+") as fh:
            fh.seek(0, os.SEEK_END)
            size = fh.tell()
            if size == 0:
                return
            # Scan backwards in growing windows until the last newline is
            # found (a torn record can exceed any fixed window).
            window = 1 << 20
            tail = b""
            last_nl = -1
            while True:
                start = max(0, size - window)
                fh.seek(start)
                chunk = fh.read(size - start)
                last_nl = chunk.rfind(b"\n")
                if last_nl >= 0 or start == 0:
                    if last_nl >= 0:
                        last_nl += start  # absolute offset
                    tail = chunk[chunk.rfind(b"\n") + 1:]
                    break
                window *= 4
            if not tail:
                # File ends on a newline: the last COMPLETE line can
                # still be a torn write (crash after the terminator of
                # a partial buffer). Validate it; trim if corrupt.
                if last_nl < 0:
                    return
                prev_nl = self._find_prev_newline(fh, last_nl)
                fh.seek(prev_nl + 1)
                line = fh.read(last_nl - prev_nl - 1)
                if not line.strip():
                    return
                try:
                    json.loads(line.decode("utf-8"))
                except (json.JSONDecodeError, UnicodeDecodeError):
                    fh.truncate(prev_nl + 1)
                return
            try:
                json.loads(tail.decode("utf-8"))
                fh.seek(0, os.SEEK_END)
                fh.write(b"\n")  # complete record missing its newline
            except (json.JSONDecodeError, UnicodeDecodeError):
                fh.truncate(size - len(tail))

    @staticmethod
    def _find_prev_newline(fh, before: int) -> int:
        """Absolute offset of the last newline strictly before
        ``before`` (-1 when the line is the file's first)."""
        window = 1 << 20
        while True:
            start = max(0, before - window)
            fh.seek(start)
            chunk = fh.read(before - start)
            nl = chunk.rfind(b"\n")
            if nl >= 0:
                return start + nl
            if start == 0:
                return -1
            window *= 4

    def apply(self, kind: str, obj, ts: float = 0.0,
              expected_generation: Optional[int] = None) -> int:
        from kueue_tpu.api.conversion import SCHEMA_VERSION

        rec = {"op": "apply", "kind": kind, "ts": ts,
               "v": SCHEMA_VERSION, "obj": to_jsonable(obj)}
        return self._stamp_and_write(rec, kind, _key_of(rec),
                                     expected_generation)

    def apply_many(self, kind: str, objs, ts: float = 0.0) -> list:
        """Batched :meth:`apply`: journal a sequence of same-kind
        objects in ONE locked append.

        Record-for-record identical to calling ``apply(kind, obj, ts)``
        per object in order — same JSON lines, same sequential
        generation stamps (repeated keys advance per occurrence) — but
        the flock / inode-chase / tail-repair / fence / disk-preflight
        / refresh round-trip is paid once per batch, and the lines land
        in a single ``write()``. The cycle commit's journal_append step
        turns N admissions into one of these.

        Returns the list of stamped generations, in input order. On
        failure (fence / degraded / ENOSPC) NO generation is recorded
        in-process: whatever full lines reached the disk sit beyond
        ``_read_offset`` and the next ``refresh()`` folds them back in,
        exactly like an append from a foreign writer.
        """
        import fcntl

        objs = list(objs)
        if not objs:
            return []
        from kueue_tpu.api.conversion import SCHEMA_VERSION

        recs = [{"op": "apply", "kind": kind, "ts": ts,
                 "v": SCHEMA_VERSION, "obj": to_jsonable(obj)}
                for obj in objs]
        self._lock_active()
        try:
            if not self._tail_is_clean():
                self._repair_torn_tail()
            if self.fence is not None and not self.fence():
                raise JournalFenced(
                    f"batched write of {len(recs)} {kind} record(s) "
                    f"refused: fence predicate failed (no longer "
                    f"leader)")
            if not self.budget.preflight(256 * len(recs)):
                raise JournalDegraded(
                    f"batched write of {len(recs)} {kind} record(s) "
                    f"refused: journal degraded read-only "
                    f"({self.budget.reason})")
            self.refresh()
            # Stamp generations into a LOCAL overlay first: the table
            # only advances after the write succeeds, so a failed batch
            # leaves in-process state untouched (refresh() self-heals
            # any lines that made it to disk).
            pending: dict = {}
            gens: list = []
            lines: list = []
            for rec in recs:
                k = (kind, _key_of(rec))
                gen = pending.get(k, self._generations.get(k, 0)) + 1
                rec["gen"] = gen
                pending[k] = gen
                gens.append(gen)
                lines.append(json.dumps(rec) + "\n")
            self._write_lines(lines)
            self._generations.update(pending)
            return gens
        finally:
            fcntl.flock(self._fh.fileno(), fcntl.LOCK_UN)

    def delete(self, kind: str, key: str, ts: float = 0.0,
               expected_generation: Optional[int] = None) -> int:
        from kueue_tpu.api.conversion import SCHEMA_VERSION

        return self._stamp_and_write(
            {"op": "delete", "kind": kind, "key": key, "ts": ts,
             "v": SCHEMA_VERSION}, kind, key, expected_generation)

    def _locked_repair(self) -> None:
        """Torn-tail repair under the shared flock: a reader must not
        truncate bytes a live writer just committed, and the repair must
        re-stat the size INSIDE the critical section."""
        import fcntl

        fcntl.flock(self._fh.fileno(), fcntl.LOCK_EX)
        try:
            self._repair_torn_tail()
        finally:
            fcntl.flock(self._fh.fileno(), fcntl.LOCK_UN)

    def _tail_is_clean(self) -> bool:
        """True when the file is empty or ends with a newline."""
        try:
            with open(self.path, "rb") as fh:
                fh.seek(0, os.SEEK_END)
                size = fh.tell()
                if size == 0:
                    return True
                fh.seek(size - 1)
                return fh.read(1) == b"\n"
        except FileNotFoundError:
            return True

    def _lock_active(self) -> None:
        """flock the ACTIVE journal file, chasing rotation renames.

        The refresh+check+append must be ATOMIC across processes, or
        two writers could both pass the generation check and clobber
        (the TOCTOU the SSA conflict contract forbids). flock makes
        the whole read-modify-append a critical section.

        Rotation renames the active file: a handle opened before the
        rotation now points at a SEALED segment, and appending there
        would land records behind ones already written to the new
        active (breaking per-key generation order). Re-check the
        inode INSIDE the lock and chase the rename. O_APPEND without
        O_CREAT: creating the path here would race the rotating
        writer's own reopen and displace its meta line.
        """
        import fcntl

        fcntl.flock(self._fh.fileno(), fcntl.LOCK_EX)
        for _ in range(64):
            try:
                if (os.fstat(self._fh.fileno()).st_ino
                        == os.stat(self.path).st_ino):
                    break
                fd = os.open(self.path, os.O_WRONLY | os.O_APPEND)
            except FileNotFoundError:
                continue  # mid-rotation window: rename done, reopen not
            fcntl.flock(self._fh.fileno(), fcntl.LOCK_UN)
            self._fh.close()
            self._fh = os.fdopen(fd, "a", encoding="utf-8")
            fcntl.flock(self._fh.fileno(), fcntl.LOCK_EX)

    def _stamp_and_write(self, rec: dict, kind: str, key: str,
                         expected_generation: Optional[int]) -> int:
        import fcntl

        self._lock_active()
        try:
            if not self._tail_is_clean():
                # Another writer crashed mid-append: truncate its torn
                # fragment (under the lock) or our record would
                # concatenate onto it and poison every later replay.
                self._repair_torn_tail()
            if self.fence is not None and not self.fence():
                raise JournalFenced(
                    f"write of {kind}/{key} refused: fence predicate "
                    f"failed (no longer leader)")
            # Disk preflight AFTER the fence (a fenced writer must hear
            # "fenced", not "disk full") and INSIDE the flock, so the
            # degrade/re-arm decision is serialized across writers.
            if not self.budget.preflight(256):
                raise JournalDegraded(
                    f"write of {kind}/{key} refused: journal degraded "
                    f"read-only ({self.budget.reason})")
            self.refresh()
            k = (kind, key)
            current = self._generations.get(k, 0)
            if (expected_generation is not None
                    and current != expected_generation):
                raise JournalConflict(kind, key, expected_generation,
                                      current)
            gen = current + 1
            rec["gen"] = gen
            self._write(rec)
            self._generations[k] = gen
            return gen
        finally:
            fcntl.flock(self._fh.fileno(), fcntl.LOCK_UN)

    def _write(self, rec: dict) -> None:
        self._write_lines([json.dumps(rec) + "\n"])

    def _write_lines(self, lines: list) -> None:
        import errno as _errno

        blob = "".join(lines)
        try:
            self._fh.write(blob)
            self._fh.flush()
            if self.fsync:
                os.fsync(self._fh.fileno())
            else:
                self._dirty = True
        except OSError as e:
            if e.errno == _errno.ENOSPC:
                # Preflight raced the filesystem: degrade instead of
                # crashing. The flushed-or-not fragment (if any) is the
                # torn tail the next locked repair truncates.
                self.budget.note_enospc(e)
                raise JournalDegraded(
                    f"append hit ENOSPC: {e}") from e
            raise
        # Our own append is already folded into the generation table —
        # advance the read offset so the next refresh() doesn't re-read
        # and re-parse it (one open+parse per record on the hot path).
        self._read_offset += len(blob.encode("utf-8"))
        self._active_lines += len(lines)

    def sync(self) -> None:
        """Crash-safe cycle boundary (Engine.schedule_once calls this
        after every non-idle cycle): flush+fsync all appends since the
        last sync. No-op when nothing is pending, so idle serving loops
        don't touch the disk. With rotation thresholds configured, the
        sealed-segment roll happens here — on the durability boundary,
        never mid-cycle."""
        if not self._dirty:
            return
        import errno as _errno
        try:
            self._fh.flush()
            os.fsync(self._fh.fileno())
        except OSError as e:
            if e.errno == _errno.ENOSPC:
                # Degrade, keep _dirty set: a later sync (after the
                # budget re-arms) retries the fsync rather than
                # silently dropping the durability boundary.
                self.budget.note_enospc(e)
                return
            raise
        self._dirty = False
        self.maybe_rotate()

    def maybe_rotate(self) -> bool:
        """Seal the active file into ``<path>.seg<NNNNNN>`` and reopen a
        fresh active when a threshold is crossed. Returns True when a
        rotation happened."""
        if not (self.rotate_bytes or self.rotate_records):
            return False
        try:
            size = os.path.getsize(self.path)
        except FileNotFoundError:
            return False
        if not ((self.rotate_bytes and size >= self.rotate_bytes)
                or (self.rotate_records
                    and self._active_lines >= self.rotate_records)):
            return False
        import fcntl

        fcntl.flock(self._fh.fileno(), fcntl.LOCK_EX)
        try:
            try:
                if (os.fstat(self._fh.fileno()).st_ino
                        != os.stat(self.path).st_ino):
                    return False  # another writer rotated first
            except FileNotFoundError:
                return False
            if not self._tail_is_clean():
                self._repair_torn_tail()
            ordinal = self.active_ordinal()
            lineage = self.lineage
            os.rename(self.path, _segment_path(self.path, ordinal))
            if MAINTENANCE_CRASH_HOOK is not None:
                MAINTENANCE_CRASH_HOOK("rotate")
            fd = os.open(self.path,
                         os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
            new_fh = os.fdopen(fd, "a", encoding="utf-8")
            line = json.dumps({"op": "meta", "kind": _META_KIND,
                               "seg": ordinal + 1,
                               "lineage": lineage}) + "\n"
            new_fh.write(line)
            new_fh.flush()
            os.fsync(fd)
            self._dir_sync()
            old = self._fh
            self._fh = new_fh
            self._read_ino = os.fstat(fd).st_ino
            self._read_offset = len(line.encode("utf-8"))
            self._active_lines = 1
            fcntl.flock(old.fileno(), fcntl.LOCK_UN)
            old.close()
            return True
        finally:
            import contextlib
            with contextlib.suppress(ValueError, OSError):
                if self._fh is not None and not self._fh.closed:
                    fcntl.flock(self._fh.fileno(), fcntl.LOCK_UN)

    def _dir_sync(self) -> None:
        """fsync the parent directory so a rename survives power loss."""
        d = os.path.dirname(self.path) or "."
        try:
            fd = os.open(d, os.O_RDONLY)
        except OSError:
            return
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    def retain_segments(self, min_ordinal: int) -> int:
        """Delete sealed segments fully covered by a checkpoint
        (ordinal < ``min_ordinal``) plus any stale-lineage leftovers.
        Returns how many files were removed."""
        lineage = self.lineage
        removed = 0
        for ordinal, seg in _sealed_segments(self.path):
            meta = _file_meta(seg)
            stale = int((meta or {}).get("lineage", 0)) != lineage
            if stale or ordinal < min_ordinal:
                try:
                    os.remove(seg)
                    removed += 1
                except FileNotFoundError:
                    pass
        return removed

    def close(self) -> None:
        if not self._fh.closed:
            self.sync()
        self._fh.close()

    def _chain(self) -> list:
        """The logical journal, in replay order: lineage-matching sealed
        segments (ordinal order) then the active file, as
        [(path, is_active)]."""
        return ([(seg, False) for _o, seg in self.sealed_segments()]
                + [(self.path, True)])

    def _replay_file(self, path: str, tolerate_torn: bool,
                     skip_lines: int = 0) -> Iterator[dict]:
        from kueue_tpu.api.conversion import upgrade_record

        try:
            with open(path, encoding="utf-8") as fh:
                lines = fh.read().split("\n")
        except FileNotFoundError:
            return
        for i, line in enumerate(lines):
            if i < skip_lines:
                continue
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                if tolerate_torn and not any(
                        rest.strip() for rest in lines[i + 1:]):
                    return  # torn tail (crash mid-write)
                raise JournalCorruption(
                    f"{path}:{i + 1}: unparseable record "
                    "with records after it") from None
            if rec.get("op") == "meta":
                continue
            yield upgrade_record(rec)

    def replay(self) -> Iterator[dict]:
        """Yield records in append order across the whole segment chain.
        A truncated/corrupt FINAL line of the ACTIVE file (crash
        mid-write) is tolerated and skipped — the same record
        __init__'s locked repair would trim; corruption anywhere else
        means records would be silently lost, so it raises
        JournalCorruption instead of dropping the tail."""
        for path, is_active in self._chain():
            yield from self._replay_file(path, tolerate_torn=is_active)

    def replay_from(self, position: dict) -> Iterator[dict]:
        """Yield only the records past a checkpoint ``position()`` —
        the O(delta-since-checkpoint) recovery suffix. Raises
        ValueError when the position's lineage doesn't match (a
        compaction rewrote history; the caller must fall back to a full
        replay)."""
        lineage = int(position.get("lineage", 0))
        segment = int(position.get("segment", 0))
        offset = int(position.get("offset", 0))
        if lineage != self.lineage:
            raise ValueError(
                f"stale position: lineage {lineage} != journal "
                f"lineage {self.lineage} (compacted since)")
        for ordinal, seg in self.sealed_segments():
            if ordinal < segment:
                continue
            yield from self._replay_file(
                seg, tolerate_torn=False,
                skip_lines=offset if ordinal == segment else 0)
        active_ord = self.active_ordinal()
        if active_ord < segment:
            raise ValueError(
                f"stale position: segment {segment} is past the active "
                f"file (ordinal {active_ord})")
        yield from self._replay_file(
            self.path, tolerate_torn=True,
            skip_lines=offset if active_ord == segment else 0)

    def compact(self) -> None:
        """Rewrite the log keeping only the last record per (kind, key),
        in first-seen order (creation order is preserved for replay).
        The compacted file starts a new LINEAGE: sealed segments and
        checkpoints taken against the old record stream are invalidated
        by the lineage bump itself, so a crash anywhere in the cleanup
        below leaves a journal that still replays to the same state.
        Not for journals under checkpoint retention (retain_segments
        deletes history this fold would need; checkpoint recovery
        subsumes compaction there)."""
        last: dict[tuple, dict] = {}
        order: list[tuple] = []
        for rec in self.replay():
            key = (rec["kind"], _key_of(rec))
            if key not in last:
                order.append(key)
            last[key] = rec
        lineage = self.lineage
        ordinal = self.active_ordinal()
        self._fh.close()
        tmp = self.path + ".compact"
        with open(tmp, "w", encoding="utf-8") as fh:
            fh.write(json.dumps({"op": "meta", "kind": _META_KIND,
                                 "seg": ordinal + 1,
                                 "lineage": lineage + 1}) + "\n")
            for key in order:
                rec = last[key]
                if rec["op"] != "delete":
                    fh.write(json.dumps(rec) + "\n")
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, self.path)
        self._dir_sync()
        if MAINTENANCE_CRASH_HOOK is not None:
            MAINTENANCE_CRASH_HOOK("compact")
        # Old-lineage segments are already dead (excluded by lineage);
        # deleting them is pure space reclamation.
        for _ordinal, seg in _sealed_segments(self.path):
            try:
                os.remove(seg)
            except FileNotFoundError:
                pass
        self._fh = open(self.path, "a", encoding="utf-8")
        # Compaction rewrites the file: re-read the generation table from
        # scratch (gens are preserved in the kept records). Compaction is
        # a leader-only operation — concurrent writers must not compact.
        self._generations.clear()
        self._seed_gens.clear()
        self._read_offset = 0
        self._active_lines = 0
        self._read_ino = os.fstat(self._fh.fileno()).st_ino
        self.refresh()


def _key_of(rec: dict) -> str:
    if rec["op"] == "delete":
        return rec["key"]
    obj = rec["obj"]
    ns = obj.get("namespace")
    name = obj.get("name", "")
    return f"{ns}/{name}" if ns is not None else name


_CREATE = {
    "cohort": "create_cohort",
    "resource_flavor": "create_resource_flavor",
    "cluster_queue": "create_cluster_queue",
    "local_queue": "create_local_queue",
    "topology": "create_topology",
    "node": "create_node",
}

# Kinds that are journaled for offline analysis but carry no engine
# state: rebuild_engine skips them BY DESIGN, not by omission. The
# tracer's per-cycle correlation record lands here — replaying it would
# double-apply nothing (it is pure rationale), and dropping it loses no
# admission. Every other emitted kind must have a _CREATE entry or an
# explicit special case above; graftlint rule R1 enforces the union.
# ``ha_digest`` is the HA failover checkpoint (kueue_tpu/ha/digest.py):
# pure verification rationale — promotion READS it, rebuild skips it.
# ``fed_route`` / ``fed_cell`` are the federation dispatcher's durable
# route intents and cell fencing epochs (kueue_tpu/federation): they
# describe WHERE workloads were sent, not engine state — the dispatcher
# folds them itself on restart; an engine rebuild must skip them.
EPHEMERAL_KINDS = frozenset(
    {"cycle_trace", "ha_digest", "fed_route", "fed_cell"})


def engine_from_records(records, engine=None, **engine_kwargs):
    """Apply a journal record sequence to an engine — the replay loop,
    factored out of rebuild_engine so HA promotion can verify a PREFIX
    of the journal (replay up to a checkpoint, assert the state digest)
    and followers can hold a read model with NO journal attached."""
    from kueue_tpu.controllers.engine import Engine

    eng = engine if engine is not None else Engine(**engine_kwargs)
    # Last op wins per (kind, key): a later delete tombstones earlier
    # applies (a node that failed must not resurrect on restart).
    live: dict[tuple, bool] = {}
    for rec in records:
        live[(rec["kind"], _key_of(rec))] = rec["op"] != "delete"
    workloads: dict[str, dict] = {}
    wl_order: list[str] = []
    clock = 0.0
    for rec in records:
        clock = max(clock, rec.get("ts", 0.0))
        kind = rec["kind"]
        key = _key_of(rec)
        if rec["op"] == "delete" or not live[(kind, key)]:
            continue
        if kind in EPHEMERAL_KINDS:
            continue
        if kind == "workload":
            if key not in workloads:
                wl_order.append(key)
            workloads[key] = rec["obj"]
            continue
        if kind == "workload_priority_class":
            eng.create_workload_priority_class(rec["obj"]["name"],
                                               rec["obj"]["value"])
            continue
        method = _CREATE.get(kind)
        if method is not None:
            getattr(eng, method)(from_jsonable(rec["obj"]))
    eng.clock = clock
    for key in wl_order:
        eng.restore_workload(from_jsonable(workloads[key]))
    return eng


def rebuild_engine(path: str, engine=None, attach_oracle: bool = False,
                   use_checkpoint: bool = True, journal_kwargs=None,
                   **engine_kwargs):
    """Cold-start an engine from a journal: the restart path. Returns
    the rebuilt engine (its caches and queues reconstructed, clock
    restored to the last persisted timestamp).

    When a sealed checkpoint exists (store/checkpoint.py), recovery is
    checkpoint base + journal suffix — O(delta-since-checkpoint), and
    the ONLY complete path once ``retain_segments`` has deleted
    history the checkpoint covers. Invalid/torn/stale checkpoints are
    skipped inside recover_records; no checkpoint at all degrades to
    the full genesis replay. ``journal_kwargs`` configures the
    re-attached writable handle (fsync, rotation thresholds)."""
    journal = Journal(path, **(journal_kwargs or {}))
    base: list = []
    meta = None
    if use_checkpoint:
        from kueue_tpu.store.checkpoint import recover_records
        base, suffix, meta = recover_records(journal)
    if meta is None:
        records = list(journal.replay())
    else:
        records = base + suffix
    eng = engine_from_records(records, engine=engine, **engine_kwargs)
    if meta is not None:
        eng.clock = max(eng.clock, float(meta.clock))
        journal.seed_generations(
            {(r["kind"], _key_of(r)): int(r.get("gen", 0))
             for r in base if r.get("gen")})
    if attach_oracle:
        eng.attach_oracle()
    # Stamp the rebuild provenance: any consumer of this engine (most
    # visibly `kueuectl explain --journal`) is answering from a
    # journal rebuild, not live scheduling state, and must be able to
    # say which position — and how old — that state is.
    eng.rebuild_position = journal.position()
    import time as _time

    eng.rebuild_wall = _time.time()
    eng.attach_journal(journal, record_existing=False)
    return eng


def attach_new_journal(engine, path: str, fsync: bool = False,
                       **journal_kwargs) -> Journal:
    """Start journaling a live engine, snapshotting its current state
    first (so a journal can be introduced after boot). Extra kwargs
    (rotate_bytes/rotate_records) configure segment rotation."""
    journal = Journal(path, fsync=fsync, **journal_kwargs)
    engine.attach_journal(journal, record_existing=True)
    return journal
