"""Workload checkpointing: npz with dtype-safe, multi-host-safe leaves.

The control plane WALs its own state (SURVEY.md section 5.4); workload
checkpointing is the service's job, and this is the pattern library:
PERMANENT gang recovery = re-place the sub-slice, restore the latest
step here, resume.

Leaves that numpy cannot round-trip (bfloat16 and friends) are stored
as their raw bits (an unsigned integer of the same width) with the
original dtype recorded, so the file costs what the state costs — a
bf16 flagship's params + adam moments are 4.9 GiB, not the 9.8 GiB a
widening to float32 wrote; global jax.Arrays that
span non-addressable devices (multi-host pjit) are gathered to the
host first.  The step stamp is "next step to run", so resume never
double-applies an update.

Writer-incarnation fencing (ADVICE round 5): recovery can relaunch a
trainer while its superseded predecessor still has one save in
flight, and two misconfigured jobs can share a CHECKPOINT_DIR.  The
old "the caller that just saved step N owns the frontier" rule let
exactly those zombies destroy the genuine latest checkpoints.  A
fenced writer claims a monotonically increasing incarnation token
(:func:`claim_incarnation`, an O_EXCL marker file so concurrent
claimers can never share one) and records it IN the checkpoint name;
save and prune then refuse to cross a NEWER incarnation's frontier —
a stale writer can only prune its own past (and its predecessors'),
never the live writer's future.

:class:`AsyncCheckpointer` is the non-blocking path: ``save()``
snapshots the tree with an asynchronously dispatched device-side copy
(safe against the train step's buffer donation) and hands it to one
background writer thread, so the step loop never waits on the host
gather or file IO.
"""

from __future__ import annotations

import json
import os
import queue
import re
import threading
from typing import Any, List, Optional, Tuple

import numpy as np

# legacy names (step_<digits>.npz) parse as incarnation 0: every
# fenced writer's past, prunable by any of them
_STEP_RE = re.compile(r"^step_(\d+)(?:\.inc_(\d+))?\.npz$")
_INC_RE = re.compile(r"^writer_(\d+)\.inc$")


class StaleWriterError(RuntimeError):
    """A writer tried to save or prune across a NEWER incarnation's
    frontier: it has been superseded (recovery relaunched the trainer,
    or another job owns the directory) and must stop writing."""


def _step_files(directory: str) -> List[Tuple[int, int, str]]:
    """[(step, incarnation, filename)] sorted by (step, incarnation).
    Only exact step_<digits>[.inc_<digits>].npz names count — a stray
    operator file (step_best.npz, a .tmp) must never crash
    saves/restores or be pruned."""
    out = []
    for name in os.listdir(directory):
        m = _STEP_RE.match(name)
        if m:
            out.append((int(m.group(1)), int(m.group(2) or 0), name))
    return sorted(out)


def _max_incarnation(directory: str) -> int:
    """Highest incarnation visible in ``directory``: claimed marker
    files AND checkpoint names (a marker could be lost to a partial
    directory copy; the checkpoints themselves still fence)."""
    top = 0
    if os.path.isdir(directory):
        for name in os.listdir(directory):
            m = _INC_RE.match(name)
            if m:
                top = max(top, int(m.group(1)))
        for _step, inc, _name in _step_files(directory):
            top = max(top, inc)
    return top


def claim_incarnation(directory: str) -> int:
    """Durably claim the next writer incarnation for ``directory``.

    The claim is an O_EXCL-created ``writer_<n>.inc`` marker, so two
    trainers racing a recovery relaunch can never share a token; the
    loser retries above the winner.  In a multi-process mesh only
    process 0 claims (it is the only writer); the token is process-0
    state, not gang state.
    """
    os.makedirs(directory, exist_ok=True)
    n = _max_incarnation(directory) + 1
    while True:
        try:
            fd = os.open(
                os.path.join(directory, f"writer_{n:010d}.inc"),
                os.O_CREAT | os.O_EXCL | os.O_WRONLY,
            )
            os.close(fd)
            return n
        except FileExistsError:
            n += 1


def _host_array(leaf: Any) -> np.ndarray:
    """Fetch a leaf to host memory, gathering multi-host arrays."""
    try:
        import jax

        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            from jax.experimental import multihost_utils

            leaf = multihost_utils.process_allgather(leaf, tiled=True)
    except ImportError:  # pragma: no cover - jax always present here
        pass
    arr = np.asarray(leaf)
    return arr


def save_checkpoint(
    directory: str, step: int, tree: Any, keep: int = 0,
    incarnation: Optional[int] = None,
) -> str:
    """Atomic save of a pytree; ``step`` = next step to run on resume.

    In a multi-process mesh call this from every process (the gather is
    collective) but only process 0 writes.

    ``keep`` > 0 prunes AFTER the new file is durably in place (write
    + fsync + rename first, delete after — a crash mid-save can
    orphan an extra file but never leaves fewer than ``keep``
    restorable steps).  Two kinds of files go: steps older than the
    newest ``keep`` at-or-below the one just saved (a long run would
    otherwise grow the directory by ~3 bytes/param per save until the
    disk fills), and steps newer than the one just saved — an
    abandoned future (operator rolled back and retrained) that would
    otherwise poison the default latest-step resume.  ``keep=0``
    prunes nothing.

    ``incarnation`` (from :func:`claim_incarnation`) fences both
    decisions: the token is recorded in the checkpoint name, saving
    raises :class:`StaleWriterError` when the directory already holds
    a NEWER incarnation's checkpoint, and pruning only ever touches
    files at-or-below this writer's incarnation — "the caller is
    authoritative about the frontier" was exactly wrong for a zombie
    writer flushing one last save after recovery relaunched a newer
    trainer (ADVICE round 5).  ``incarnation=None`` keeps the legacy
    unfenced behavior for single-writer tools.
    """
    import jax

    leaves, _ = jax.tree.flatten(tree)
    arrays = {}
    dtypes = {}
    for i, leaf in enumerate(leaves):
        arr = _host_array(leaf)
        if arr.dtype.kind not in "fiub":
            # numpy's npz cannot round-trip extension dtypes (ml_dtypes
            # bfloat16 reads back as void): store the bits and remember
            dtypes[str(i)] = arr.dtype.name
            arr = arr.view(f"u{arr.dtype.itemsize}")
        arrays[f"leaf_{i}"] = arr

    if getattr(jax, "process_index", lambda: 0)() != 0:
        return ""
    os.makedirs(directory, exist_ok=True)
    if incarnation is not None and _max_incarnation(directory) > incarnation:
        # a newer writer owns this directory: the zombie must neither
        # overwrite the live frontier nor (below) prune it.  In a gang
        # only process 0 sees the directory, so only process 0 raises;
        # its task death makes the scheduler reap and recover the
        # whole gang (the AsyncCheckpointer path instead agrees on the
        # fence gang-wide and skips uniformly — see save()).
        raise StaleWriterError(
            f"writer incarnation {incarnation} superseded by "
            f"{_max_incarnation(directory)} in {directory}; refusing "
            "to save — recovery relaunched a newer trainer"
        )
    suffix = (
        "" if incarnation is None else f".inc_{incarnation:010d}"
    )
    path = os.path.join(directory, f"step_{step:010d}{suffix}.npz")
    tmp = path + ".tmp"
    meta = json.dumps({
        "dtypes": dtypes, "step": step,
        "incarnation": incarnation or 0,
    }).encode()
    try:
        with open(tmp, "wb") as f:
            np.savez(
                f, __meta__=np.frombuffer(meta, dtype=np.uint8), **arrays
            )
            f.flush()
            os.fsync(f.fileno())
    except BaseException:
        # a write the disk refused (full, or a file-size limit) must
        # not go on holding the space the next save needs
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    os.replace(tmp, path)
    if keep > 0:
        # prune by the LISTED names (not reconstructed ones): a
        # hand-named step_5.npz must actually be removed, and a
        # non-matching stray file must never crash the save.  The
        # just-saved step anchors the frontier: retention counts the
        # newest `keep` AT OR BELOW it (so this call's own file is
        # never deleted — review r5), and anything ABOVE it is an
        # abandoned future from a rollback, pruned so the default
        # latest-step resume cannot restore the state the rollback
        # was meant to undo (review r5, follow-up).  Fencing: only
        # files from THIS incarnation or older are candidates — a
        # newer writer's files are the live frontier, not our
        # abandoned future (unreachable when the save-fence above
        # raised, load-bearing when the newer file landed between
        # that check and this scan).
        mine = incarnation if incarnation is not None else float("inf")
        files = [
            (s, i, n) for s, i, n in _step_files(directory) if i <= mine
        ]
        older = [(s, n) for s, i, n in files if s <= step]
        stale_future = [(s, n) for s, i, n in files if s > step]
        for _s, name in older[:-keep] + stale_future:
            try:
                os.remove(os.path.join(directory, name))
            except OSError:
                pass  # already gone (concurrent pruner) — harmless
    return path


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    files = _step_files(directory)
    return files[-1][0] if files else None


def restore_checkpoint(
    directory: str, like: Any, step: Optional[int] = None
) -> Tuple[Any, Optional[int]]:
    """Restore into the structure of ``like``; returns (tree, step) or
    (like, None) when no checkpoint exists.  Each leaf is cast back to
    ``like``'s dtype (jnp handles bfloat16 casts numpy cannot)."""
    import jax
    import jax.numpy as jnp

    files = _step_files(directory) if os.path.isdir(directory) else []
    target = step if step is not None else (
        files[-1][0] if files else None
    )
    if target is None:
        return like, None
    # open the LISTED filename for the step: a hand-named step_5.npz
    # (unpadded) must restore, not 404 on a reconstructed name.  With
    # same-step files from several incarnations, the NEWEST
    # incarnation's wins (the sort is (step, incarnation)).
    names = [name for s, _inc, name in files if s == target]
    if not names:
        # an EXPLICITLY requested step that is absent is an error,
        # not a silent fresh-start (step is not None here: the
        # latest-step path only yields steps that exist)
        raise FileNotFoundError(
            f"no checkpoint for step {step} in {directory}"
        )
    data = np.load(os.path.join(directory, names[-1]))
    stored_as_bits = {}
    if "__meta__" in data.files:
        stored_as_bits = json.loads(bytes(data["__meta__"])).get("dtypes", {})
    leaves, treedef = jax.tree.flatten(like)
    restored = []
    for i, leaf in enumerate(leaves):
        arr = data[f"leaf_{i}"]
        if str(i) in stored_as_bits and arr.dtype.kind == "u":
            # the bits of an extension dtype (older files hold such
            # leaves widened to float32: the cast below covers those)
            import ml_dtypes

            arr = arr.view(getattr(ml_dtypes, stored_as_bits[str(i)]))
        if hasattr(leaf, "dtype"):
            restored.append(jnp.asarray(arr).astype(leaf.dtype))
        else:
            restored.append(arr)
    return jax.tree.unflatten(treedef, restored), target


_JIT_COPY = None


def _snapshot_tree(tree: Any) -> Any:
    """Device-side copy of a pytree, dispatched as ONE fused program.

    The copies are enqueued BEFORE the train loop's next dispatch
    donates the source buffers, so the background writer reads stable
    values while the step loop overwrites the originals in place.
    Fused matters: a per-leaf ``jnp.copy`` pays one dispatch per leaf
    (~10ms for a 34-leaf adam state on a syscall-bound host — most of
    a small step); one jitted tree-copy pays one.  Trees with non-jax
    leaves fall back to per-leaf host copies."""
    global _JIT_COPY
    import jax

    if all(
        isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(tree)
    ):
        if _JIT_COPY is None:
            import jax.numpy as jnp

            _JIT_COPY = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        return _JIT_COPY(tree)
    return jax.tree.map(lambda leaf: np.copy(np.asarray(leaf)), tree)


class AsyncCheckpointer:
    """Non-blocking, incarnation-fenced checkpoint writer.

    ``save(step, tree)`` costs the step loop only an async device-side
    copy per leaf; one background thread then gathers to host and runs
    :func:`save_checkpoint` (write + fsync + rename + fenced prune)
    off the hot path.  The queue is BOUNDED: saving faster than the
    disk drains backpressures ``save()`` instead of hoarding
    snapshots.  ONE snapshot at a time is device-resident: it is a
    full copy of params + optimizer state in HBM, and at chip-filling
    sizes a second one does not fit (the 872M flagship on a 16 GB v5e
    died RESOURCE_EXHAUSTED at its second save).  The writer moves a
    snapshot to host memory FIRST and frees the device copy before
    the slow npz write; ``save()`` waits for that, not for the disk.

    Fencing: the writer claims an incarnation up front (or is handed
    one).  The first save that hits a newer incarnation's frontier
    marks the checkpointer ``fenced`` and every later save drops
    immediately — a zombie trainer must stop fighting the live writer,
    not retry.  Write failures land in ``errors`` (telemetry-grade:
    training continues; the operator reads the list via ``wait()``).

    Multi-process contract is :func:`save_checkpoint`'s: every
    process must call ``save()`` in the same order (the multi-host
    gather runs inside ``save()``, in program order with the training
    collectives), and only process 0 writes; claim the incarnation on
    process 0 and broadcast it so the gang agrees on one token.
    """

    def __init__(
        self, directory: str, keep: int = 0,
        incarnation: Optional[int] = None, max_pending: int = 2,
    ):
        import jax

        self.directory = directory
        self.keep = keep
        if incarnation is None and (
            getattr(jax, "process_index", lambda: 0)() == 0
        ):
            incarnation = claim_incarnation(directory)
        self.incarnation = incarnation
        self.errors: List[str] = []
        self.saved: List[str] = []
        # the fence latch flips on BOTH sides of the queue: the writer
        # thread latches on StaleWriterError, the caller latches on
        # the broadcast verdict — a lock keeps the flip ordered (reads
        # stay lock-free: the latch is monotonic False -> True)
        self._fence_lock = threading.Lock()
        self.fenced = False
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, max_pending))
        # held from the device copy until the writer has it on host
        self._device_slot = threading.Semaphore(1)
        self._thread = threading.Thread(
            target=self._drain, name="async-ckpt", daemon=True
        )
        self._thread.start()

    def save(self, step: int, tree: Any) -> None:
        """Snapshot ``tree`` (async device copy) and enqueue the write;
        returns as soon as the copies are DISPATCHED.

        Multi-host leaves (non-addressable global arrays) force the
        gather HERE, on the caller's thread: ``process_allgather`` is
        a collective, and a collective issued from the writer thread
        would race the training loop's collectives in program order —
        a cross-host deadlock waiting to happen.  The gang pays the
        gather synchronously (exactly what the blocking path paid);
        the npz write + fsync + prune still overlap the step loop.

        The FENCE decision is gang-uniform too: only process 0
        observes the directory, so its fenced latch is broadcast and
        every process skips the same saves — a process-0-local skip
        would leave the peers alone in the gather collective and wedge
        the gang (review r7)."""
        import jax

        multi_host = any(
            isinstance(leaf, jax.Array) and not leaf.is_fully_addressable
            for leaf in jax.tree.leaves(tree)
        )
        if multi_host:
            import jax.numpy as jnp
            from jax.experimental import multihost_utils

            fenced = bool(int(multihost_utils.broadcast_one_to_all(
                jnp.int32(int(self.fenced))
            )))
            if fenced:
                with self._fence_lock:
                    self.fenced = True
                return
        elif self.fenced:
            return
        if multi_host:
            # on host before this returns: never a queued device copy
            snapshot = jax.tree.map(_host_array, _snapshot_tree(tree))
        else:
            self._device_slot.acquire()
            try:
                snapshot = _snapshot_tree(tree)
            except BaseException:
                self._device_slot.release()
                raise
        self._queue.put((step, snapshot, not multi_host))

    def _drain(self) -> None:
        import jax

        while True:
            item = self._queue.get()
            try:
                if item is None:
                    return
                step, snapshot, on_device = item
                del item
                try:
                    if on_device:
                        try:
                            snapshot = jax.tree.map(_host_array, snapshot)
                        finally:
                            self._device_slot.release()
                    self.saved.append(save_checkpoint(
                        self.directory, step, snapshot, keep=self.keep,
                        incarnation=self.incarnation,
                    ))
                except StaleWriterError as e:
                    with self._fence_lock:
                        self.fenced = True
                    self.errors.append(str(e))
                except Exception as e:  # noqa: BLE001 — a failed save
                    # (full disk, NFS hiccup) must not kill the writer
                    # thread: later saves may land, and the step loop
                    # reads the failure from .errors
                    self.errors.append(repr(e))
            finally:
                self._queue.task_done()

    def wait(self) -> List[str]:
        """Block until every enqueued save is durable (or failed);
        returns accumulated error strings."""
        self._queue.join()
        return list(self.errors)

    def close(self) -> List[str]:
        """Drain pending saves, stop the writer thread, return errors."""
        self._queue.put(None)
        self._thread.join()
        return list(self.errors)
