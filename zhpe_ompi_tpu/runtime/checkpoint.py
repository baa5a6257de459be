"""Checkpoint/restart — the crs/crcp lineage re-imagined as async array
snapshots.

Reference shape (SURVEY.md §5): ``opal/mca/crs/{none,self}`` single-process
checkpoint, ``ompi/mca/crcp/bkmrk`` message bookmarking,
``vprotocol/pessimist`` message logging, CLIs ``opal-checkpoint`` /
``opal-restart``.  That machinery exists because MPI processes carry
in-flight wire state that must be quiesced or logged.  On a
single-controller SPMD machine the program state IS a pytree of arrays
between steps, so the idiomatic equivalent (noted in SURVEY.md §5) is an
orbax-style async snapshot:

- ``Checkpointer.save(step, state)`` snapshots device arrays to host, then
  writes in a background thread (computation overlaps IO — the reason the
  reference interleaves checkpoint with the progress engine).
- Atomicity via the write-to-tmp-then-rename protocol; a crashed writer
  leaves only a ``.tmp`` directory that restore ignores (crs/self's
  handshake analog).
- ``restore()`` returns the newest complete checkpoint; retention keeps
  the last k (``keep``).
- The host-plane contract replacing crcp/bkmrk: checkpoint at a quiescent
  point (no outstanding host-plane requests); :func:`quiesce_check` makes
  the contract checkable instead of implicit.

Arrays are stored via :mod:`zhpe_ompi_tpu.io.sharded`, so a sharded state
restores with each device reading only its extent.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading

import numpy as np

import jax

from ..core import errors
from ..io import sharded
from ..mca import output as mca_output
from . import flightrec

_stream = mca_output.open_stream("checkpoint")

_STEP_PREFIX = "step_"


def quiesce_check() -> None:
    """Raise if host-plane pt2pt queues are non-empty (the checkable form
    of crcp/bkmrk's 'drain in-flight messages first' protocol).

    FT-aware: rows attributable to ACKED-failed ranks are exempt — a
    dead rank's own queues, posted receives named on it (abandoned by
    typed-failure delivery), and unexpected messages from it can never
    drain, and the rollback owns them; without the exemption a
    checkpoint could never be declared quiescent during recovery.  The
    ack is the gate: an unacknowledged failure still blocks, exactly as
    its pending wildcard receives do."""
    from ..pt2pt import universe as uni_mod

    def depths():
        return (uni_mod._queue_depth("posted", exempt_acked_failed=True),
                uni_mod._queue_depth("unexpected", exempt_acked_failed=True))

    posted, unexpected = depths()
    if posted or unexpected:
        # an unreachable universe stays in the live WeakSet until the
        # cyclic GC runs, and its abandoned queues are not in flight
        gc.collect()
        posted, unexpected = depths()
    if posted or unexpected:
        raise errors.InternalError(
            f"checkpoint at non-quiescent point: {posted} posted recvs, "
            f"{unexpected} unexpected messages in flight"
        )


class Checkpointer:
    """Async checkpoint manager over a directory."""

    def __init__(self, directory: str, keep: int = 3,
                 check_quiescent: bool = True):
        self.directory = directory
        self.keep = keep
        self.check_quiescent = check_quiescent
        os.makedirs(directory, exist_ok=True)
        self._worker: threading.Thread | None = None
        self._error: BaseException | None = None
        # one checkpointer is SHARED by every survivor thread of the
        # recovery pipeline (each calls rollback() concurrently): the
        # reentrant lock serializes save/wait/restore/heal so a pair of
        # concurrent restores cannot double-join the worker or race the
        # .old → final republish heal
        self._op_lock = threading.RLock()
        self._heal_interrupted()

    def _heal_interrupted(self) -> None:
        """Complete — backwards — any republish a crashed writer left
        half done.  The re-checkpoint protocol retires the existing
        version to ``step_N.old`` before publishing the new one; a
        writer killed between those two renames leaves ``step_N.old``
        with no ``step_N`` — the retired version IS the newest complete
        checkpoint for that step, so put it back.  ``step_N.old`` WITH a
        ``step_N`` means the publish landed and only the cleanup was
        lost: drop the stale copy.  ``.tmp`` partials need no healing —
        all_steps ignores them and the next writer of that step clears
        them."""
        with self._op_lock:
            for name in os.listdir(self.directory):
                if not (name.startswith(_STEP_PREFIX)
                        and name.endswith(".old")):
                    continue
                old = os.path.join(self.directory, name)
                final = old[:-len(".old")]
                if os.path.isdir(final):
                    shutil.rmtree(old, ignore_errors=True)
                else:
                    os.replace(old, final)
                    mca_output.verbose(
                        1, _stream,
                        "healed interrupted republish: restored %s", final,
                    )

    # -- save ------------------------------------------------------------

    def save(self, step: int, state, blocking: bool = False) -> None:
        """Snapshot `state` (a pytree of arrays) at `step`.  Device→host
        transfer happens NOW (so the caller may donate/overwrite buffers);
        disk writes happen in the background unless `blocking`."""
        if self.check_quiescent:
            quiesce_check()
        with self._op_lock:
            # zlint: disable=ZL002 -- PR 2 contract: save/wait/restore serialize under ONE RLock; the joined writer never takes it (no cycle) and callers accept checkpoint-grade latency
            self.wait()  # one outstanding checkpoint at a time (orbax)
            flightrec.record(flightrec.CKPT_BEGIN, step=int(step),
                             plane="serial")
            leaves, treedef = jax.tree_util.tree_flatten(state)
            # snapshot to host before returning control (np.array COPIES
            # even for host leaves — the caller may overwrite its buffers
            # right away).  Single-controller semantics: the controller
            # materializes each full array; sharded RESTORE still places
            # per-device extents directly.
            host_leaves = [np.array(leaf) for leaf in leaves]

            def write():
                try:
                    self._write(step, host_leaves, treedef)
                except BaseException as e:  # noqa: BLE001 - see wait()
                    self._error = e

            if blocking:
                write()
                self._raise_pending()
            else:
                self._worker = threading.Thread(target=write, daemon=True)
                self._worker.start()

    def _write(self, step, host_leaves, treedef) -> None:
        final = os.path.join(self.directory, f"{_STEP_PREFIX}{step}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        for i, leaf in enumerate(host_leaves):
            sharded.save_sharded(os.path.join(tmp, f"leaf_{i}.zmpi"), leaf)
        meta = {
            "step": step,
            "n_leaves": len(host_leaves),
            "treedef": str(treedef),
        }
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
        # pytree structure, restorable without the original code layout
        with open(os.path.join(tmp, "treedef.pkl"), "wb") as f:
            import pickle

            pickle.dump(treedef, f)
        if os.path.isdir(final):
            # re-checkpointing a step (crash-restart reruns it): retire the
            # old version first; rename below republishes atomically
            old = final + ".old"
            if os.path.exists(old):
                shutil.rmtree(old)
            os.replace(final, old)
            os.replace(tmp, final)
            shutil.rmtree(old, ignore_errors=True)
        else:
            os.replace(tmp, final)  # atomic publish
        flightrec.record(flightrec.CKPT_COMMIT, step=int(step),
                         plane="serial")
        mca_output.verbose(1, _stream, "checkpoint step %d written", step)
        self._retain()

    def _retain(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep > 0 else []:
            shutil.rmtree(
                os.path.join(self.directory, f"{_STEP_PREFIX}{s}"),
                ignore_errors=True,
            )

    # -- wait/err --------------------------------------------------------

    def wait(self) -> None:
        """Block until the outstanding async save completes; re-raise its
        error if it failed."""
        with self._op_lock:
            self._join_worker()
            self._raise_pending()

    def _join_worker(self) -> None:
        """Join the outstanding writer WITHOUT surfacing its error —
        restore() must not let a failed save poison a rollback (the
        failed write left only partials, which the heal/all_steps
        contract already ignores); the error stays pending for the next
        save()/wait() to report."""
        with self._op_lock:
            if self._worker is not None:
                # zlint: disable=ZL002 -- PR 2 contract: the writer thread never takes _op_lock, so this join cannot cycle; holding it is WHY concurrent restores can't double-join
                self._worker.join()
                self._worker = None

    def _raise_pending(self) -> None:
        if self._error is not None:
            e, self._error = self._error, None
            raise errors.InternalError(f"checkpoint write failed: {e!r}")

    # -- restore ---------------------------------------------------------

    def all_steps(self) -> list[int]:
        """Complete checkpoints, ascending (ignores .tmp partials)."""
        out = []
        for name in os.listdir(self.directory):
            if name.startswith(_STEP_PREFIX) and not name.endswith(".tmp"):
                try:
                    out.append(int(name[len(_STEP_PREFIX):]))
                except ValueError:
                    continue
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: int | None = None, shardings=None):
        """Load a checkpoint (default: newest).  `shardings`: optional
        pytree-of-shardings matching the state — each leaf then
        materializes directly onto its devices (the rejoined-rank
        restore path: a replacement reads only its extents).  Heals
        interrupted republishes first, so a writer crashed mid-swap
        still yields the previous complete step, never a partial; a
        FAILED async save does not poison the restore (its error stays
        pending for the next save/wait) — the rollback gets the newest
        COMPLETE checkpoint either way."""
        with self._op_lock:
            # an in-flight async writer must not race the heal; its
            # failure is not ours to report (see _join_worker).  The
            # lock spans the read too: a concurrent save republishing
            # this very step must not swap directories under the reader.
            self._join_worker()
            self._heal_interrupted()
            if step is None:
                step = self.latest_step()
                if step is None:
                    raise errors.ArgError(
                        f"no checkpoint found in {self.directory}"
                    )
            d = os.path.join(self.directory, f"{_STEP_PREFIX}{step}")
            if not os.path.isdir(d):
                raise errors.ArgError(f"no checkpoint for step {step}")
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
            with open(os.path.join(d, "treedef.pkl"), "rb") as f:
                import pickle

                treedef = pickle.load(f)
            # None is a valid per-leaf sharding ("load to host") and
            # must keep its slot: the default flatten DROPS None
            # leaves, which would pair the remaining shardings with
            # the wrong arrays (found by the survivor-mesh restore
            # tests: a {"w": sharding, "step_count": None} tree)
            shard_leaves = (
                jax.tree_util.tree_flatten(
                    shardings, is_leaf=lambda x: x is None)[0]
                if shardings is not None else [None] * meta["n_leaves"]
            )
            leaves = [
                sharded.load_sharded(
                    os.path.join(d, f"leaf_{i}.zmpi"), shard_leaves[i]
                )
                for i in range(meta["n_leaves"])
            ]
            return jax.tree_util.tree_unflatten(treedef, leaves), step
