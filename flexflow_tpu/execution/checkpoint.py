"""Preemption-safe checkpoint / resume: atomic sharded checkpoints.

The reference leans on Legion's resilient task runtime and ships no model
checkpoint format (SURVEY §5) — only get_tensor/set_tensor weight access
(parallel_tensor.cc:650,698) and strategy export (--export-strategy). This
module is the TPU-native resilience equivalent (ISSUE 4), built for training
on *preemptible* TPU pools where a SIGTERM can land at any step:

* **Atomic commit**: every checkpoint is staged in a ``step_N.tmp.<pid>``
  directory, fsynced, stamped with a ``COMMIT`` marker (carrying the
  checksum of ``meta.json``), and renamed into place. A killed writer can
  only ever leave a ``.tmp`` directory behind; ``latest_checkpoint`` ignores
  anything without a valid marker, so resume never reads a torn checkpoint.
* **Content checksums**: ``meta.json`` records a crc32 per payload file;
  ``restore_checkpoint`` verifies them before touching model state and
  raises ``CheckpointCorruptError`` on any mismatch (bit rot, truncation,
  a half-copied rsync).
* **Background async save**: ``CheckpointManager`` snapshots the
  params/opt_state pytrees with cheap *device-side copies* (donation-safe:
  the jitted step donates its input buffers, so holding references to the
  live trees across a step would read freed buffers) and serializes them on
  a worker thread — the step loop never blocks on host transfer or disk.
  The hand-off queue is bounded; when serialization falls behind, the next
  ``save_async`` blocks (backpressure) instead of accumulating unbounded
  snapshot memory.
* **Retention**: ``prune_checkpoints`` keeps the newest N committed
  checkpoints (``--keep-checkpoints``) and sweeps stale ``.tmp`` staging
  dirs.
* **Exact resume**: ``train_state.json`` carries the data-pipeline cursor
  (epoch, batch-in-epoch, rng counter, global step) so ``--resume auto``
  continues the exact sample stream and dropout key sequence.

Tensor payloads go through orbax; ``restore_checkpoint`` builds orbax
``restore_args`` from the compiled model's *current* shardings (each shard
lands directly on its owner device, no host gather) and accepts a ``mesh=``
override — the degraded-topology path (``resilience/elastic.py``) restores
host-staged onto a freshly searched strategy. See ``docs/fault_tolerance.md``.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from queue import Queue
from typing import Any, Dict, List, Optional, Tuple

# low-level durable-io idioms extracted to utils/durable_io.py (ISSUE 20:
# one implementation shared with the serving request journal); the old
# underscore names stay importable — they are this module's API to the
# chaos harness and the resilience tests
from ..utils.durable_io import (STALE_TMP_AGE_S,  # noqa: F401
                                crc_file as _crc_file,
                                fsync_path as _fsync_path,
                                write_json as _write_json)

COMMIT_MARKER = "COMMIT"
_STEP_RE = re.compile(r"^step_(\d+)$")
_FORMAT_VERSION = 1


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed commit-marker or checksum validation."""


def _payload_files(root: str) -> List[str]:
    """Relative paths of every checksummed file under a staged checkpoint
    (everything except meta.json and the commit marker, which carry the
    checksums / the checksum-of-checksums)."""
    out = []
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            rel = os.path.relpath(os.path.join(dirpath, fn), root)
            if rel in ("meta.json", COMMIT_MARKER):
                continue
            out.append(rel)
    return sorted(out)


def _dir_checksums(root: str) -> Dict[str, List[int]]:
    return {rel: list(_crc_file(os.path.join(root, rel)))
            for rel in _payload_files(root)}


# ----------------------------------------------------------------- snapshots
def _device_snapshot(tree):
    """Donation-safe snapshot: a device-side copy of every jax array leaf.

    The training step is jitted with ``donate_argnums=(0, 1)`` — the params
    and opt_state buffers handed to the *next* step are invalidated by it, so
    a checkpoint writer cannot hold references to the live trees across
    steps. A device copy is cheap (HBM bandwidth, dispatched async) and the
    copy is never fed back into the step, so the background writer can read
    it whenever the disk catches up (Check-N-Run's decoupled-snapshot idea,
    NSDI'22)."""
    import jax
    import jax.numpy as jnp

    def snap(x):
        if isinstance(x, jax.Array):
            return jnp.copy(x)
        return x

    return jax.tree_util.tree_map(snap, tree)


# The FF002 donation-aliasing contract (analysis/rules.
# donation_spec_for_training) reads this flag rather than hardcoding it:
# it is True because CheckpointManager.save_async routes every retained
# tree through _device_snapshot above. Bypass the snapshot (or flip this
# without doing so) and ShardLint flags the post-step reference to a
# donated buffer — the PR 4 bug class.
SNAPSHOT_DEVICE_COPY = True


# -------------------------------------------------------------------- saving
def save_checkpoint(ffmodel, directory: str, step: int = 0,
                    train_state: Optional[Dict[str, Any]] = None,
                    params=None, opt_state=None) -> str:
    """Atomically save params + optimizer state + strategy + metadata.

    Protocol: stage everything under ``step_N.tmp.<pid>``, fsync the
    payloads, write ``meta.json`` (step, mesh topology, per-file crc32s),
    write the ``COMMIT`` marker (crc of meta.json), fsync, then rename the
    staging dir to ``step_N`` and fsync the parent. A crash at any point
    leaves either the previous committed ``step_N`` or an ignorable
    ``.tmp`` dir — never a torn checkpoint.

    ``params``/``opt_state`` default to the live model trees; the async
    manager passes donation-safe snapshots instead. ``train_state`` is the
    exact-resume cursor (epoch, batch_in_epoch, rng_counter, step).
    """
    import orbax.checkpoint as ocp

    params = ffmodel.params if params is None else params
    opt_state = ffmodel.opt_state if opt_state is None else opt_state
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{int(step)}")
    tmp = f"{final}.tmp.{os.getpid()}"
    if os.path.isdir(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    try:
        ckptr = ocp.PyTreeCheckpointer()
        ckptr.save(os.path.join(tmp, "params"), params, force=True)
        ckptr.save(os.path.join(tmp, "opt_state"), opt_state, force=True)
        with open(os.path.join(tmp, "strategy.json"), "w") as f:
            f.write(ffmodel.strategy.to_json(ffmodel.pcg))
        if train_state is not None:
            _write_json(os.path.join(tmp, "train_state.json"),
                        train_state, fsync=False)
        for rel in _payload_files(tmp):
            _fsync_path(os.path.join(tmp, rel))
        import numpy as np

        meta = {
            "format_version": _FORMAT_VERSION,
            "step": int(step),
            "mesh_shape": list(ffmodel.strategy.mesh_shape),
            "axis_names": list(ffmodel.strategy.axis_names),
            "n_devices": int(np.prod(ffmodel.strategy.mesh_shape)),
            "checksums": _dir_checksums(tmp),
        }
        _write_json(os.path.join(tmp, "meta.json"), meta)
        meta_crc, _ = _crc_file(os.path.join(tmp, "meta.json"))
        _write_json(os.path.join(tmp, COMMIT_MARKER),
                    {"meta_crc32": meta_crc})
        _fsync_path(tmp)
        if os.path.isdir(final):  # overwrite semantics (re-save of a step)
            shutil.rmtree(final)
        os.replace(tmp, final)
        _fsync_path(directory)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return final


# ----------------------------------------------------------------- inspection
def read_meta(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, "meta.json")) as f:
        return json.load(f)


def read_train_state(path: str) -> Optional[Dict[str, Any]]:
    p = os.path.join(path, "train_state.json")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


def restore_train_cursor(ffmodel, path: str) -> Dict[str, Any]:
    """Apply the exact-resume cursor recorded in ``train_state.json`` to the
    model (today: the rng counter, so dropout key streams replay) and return
    the cursor dict ({} when the checkpoint has none). THE single
    implementation — resume, rollback and elastic restart all go through
    here, so a new cursor field is restored on every path at once."""
    ts = read_train_state(path) or {}
    if "rng_counter" in ts:
        ffmodel._rng_counter = int(ts["rng_counter"])
    return ts


def is_committed(path: str) -> bool:
    """Commit-marker check: the marker must exist and its recorded crc must
    match the on-disk ``meta.json`` (a marker copied next to a torn meta
    does not count).

    Migration: checkpoints written by the pre-atomic format carry no
    marker (and no ``format_version``/``checksums`` in meta) — an intact
    legacy checkpoint is accepted as committed rather than mislabeled a
    partial write; torn legacy writes were never detectable, which is
    unchanged. Anything whose meta declares ``format_version`` REQUIRES
    its marker."""
    marker = os.path.join(path, COMMIT_MARKER)
    meta = os.path.join(path, "meta.json")
    if not os.path.isfile(meta):
        return False
    if not os.path.isfile(marker):
        try:
            with open(meta) as f:
                m = json.load(f)
            return "format_version" not in m and "step" in m
        except (OSError, ValueError):
            return False
    try:
        with open(marker) as f:
            want = json.load(f)["meta_crc32"]
        got, _ = _crc_file(meta)
        return int(want) == got
    except (OSError, ValueError, KeyError):
        return False


def verify_checkpoint(path: str) -> List[str]:
    """Re-checksum every payload file against ``meta.json``. Returns the
    list of bad entries (missing / size or crc mismatch); empty = intact."""
    try:
        sums = read_meta(path).get("checksums", {})
    except (OSError, ValueError):
        return ["meta.json"]
    bad = []
    for rel, (crc, size) in sums.items():
        fp = os.path.join(path, rel)
        if not os.path.isfile(fp):
            bad.append(rel)
            continue
        got_crc, got_size = _crc_file(fp)
        if got_crc != int(crc) or got_size != int(size):
            bad.append(rel)
    return bad


def list_checkpoints(directory: str) -> List[Tuple[int, str]]:
    """Committed checkpoints as sorted [(step, path)]; uncommitted or
    garbage directories (``.tmp`` staging, partial writes, stray names)
    are skipped."""
    if not os.path.isdir(directory):
        return []
    out = []
    for d in os.listdir(directory):
        m = _STEP_RE.match(d)
        if not m:
            continue
        path = os.path.join(directory, d)
        if os.path.isdir(path) and is_committed(path):
            out.append((int(m.group(1)), path))
    return sorted(out)


def latest_checkpoint(directory: str, verify: bool = False
                      ) -> Optional[str]:
    """Newest *committed* checkpoint, or None. Partially written
    directories (no/bad commit marker) are skipped, not selected and not
    crashed on. With ``verify=True`` checksums are also required, so a
    corrupted-latest falls back to the previous good checkpoint."""
    for _step, path in reversed(list_checkpoints(directory)):
        if verify and verify_checkpoint(path):
            continue
        return path
    return None


def prune_checkpoints(directory: str, keep: int) -> List[str]:
    """Delete all but the newest ``keep`` committed checkpoints; also sweeps
    ``.tmp`` staging dirs from dead writers (other pids, untouched for
    ``STALE_TMP_AGE_S``) via the shared ``utils.durable_io`` sweep.
    Returns removed paths."""
    from ..utils.durable_io import sweep_stale_tmp

    removed = []
    if keep <= 0 or not os.path.isdir(directory):
        return removed
    commits = list_checkpoints(directory)
    for _step, path in commits[:-keep] if len(commits) > keep else []:
        shutil.rmtree(path, ignore_errors=True)
        removed.append(path)
    removed.extend(sweep_stale_tmp(directory))
    return removed


# ------------------------------------------------------------------ restoring
def _leaf_restore_args(leaf, mesh=None):
    import jax
    import orbax.checkpoint as ocp

    if isinstance(leaf, jax.Array):
        sh = leaf.sharding
        if mesh is not None:
            from jax.sharding import NamedSharding

            if isinstance(sh, NamedSharding) and sh.mesh is not mesh:
                sh = NamedSharding(mesh, sh.spec)
        return ocp.ArrayRestoreArgs(sharding=sh, global_shape=leaf.shape,
                                    dtype=leaf.dtype)
    return ocp.RestoreArgs()


def _host_staged_restore(ckptr, subdir: str, template):
    """Topology-changing restore: read every leaf to host numpy, then
    ``device_put`` it onto the *template's* sharding (the freshly searched
    strategy's placement). The host bounce is the price of resharding onto
    a mesh the checkpoint was not written for."""
    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    ra = jax.tree_util.tree_map(
        lambda l: (ocp.RestoreArgs(restore_type=np.ndarray)
                   if isinstance(l, jax.Array) else ocp.RestoreArgs()),
        template)
    host = ckptr.restore(subdir, item=template, restore_args=ra)

    def put(h, t):
        if isinstance(t, jax.Array):
            return jax.device_put(np.asarray(h), t.sharding)
        return h

    return jax.tree_util.tree_map(put, host, template)


def restore_checkpoint(ffmodel, path: str, mesh=None,
                       verify: bool = True) -> int:
    """Restore into a compiled model; shards land directly on their owner
    devices via orbax ``restore_args`` built from the model's current
    shardings (params from the executor's strategy placement, opt_state
    from its live leaves).

    ``mesh=`` overrides the target mesh for every NamedSharding (the
    elastic-restart path); when the checkpoint's recorded topology differs
    from the target, the pytree is restored host-staged and resharded onto
    the current strategy instead (``resilience/elastic.py`` drives the
    re-search that makes that strategy). ``verify`` checks content
    checksums first — a corrupt checkpoint raises before any model state
    is touched. Returns the checkpoint's step."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    if not is_committed(path):
        raise CheckpointCorruptError(
            f"{path}: no valid commit marker (partial write or not a "
            "checkpoint) — refusing to restore")
    if verify:
        bad = verify_checkpoint(path)
        if bad:
            raise CheckpointCorruptError(
                f"{path}: checksum mismatch in {bad} — checkpoint is "
                "corrupt; restore from an earlier committed step")
    meta = read_meta(path)
    target_mesh = mesh if mesh is not None else ffmodel.mesh
    same_topology = (ffmodel.strategy is not None and
                     list(meta.get("mesh_shape", [])) ==
                     list(ffmodel.strategy.mesh_shape) and mesh is None)
    ckptr = ocp.PyTreeCheckpointer()
    import jax

    if same_topology or mesh is not None:
        try:
            for attr, subdir in (("params", "params"),
                                 ("opt_state", "opt_state")):
                template = getattr(ffmodel, attr)
                ra = jax.tree_util.tree_map(
                    lambda l: _leaf_restore_args(l, mesh), template)
                setattr(ffmodel, attr,
                        ckptr.restore(os.path.join(path, subdir),
                                      item=template, restore_args=ra))
            return int(meta["step"])
        except (ValueError, KeyError) as e:
            # a mesh= override whose axes don't exist in the saved specs
            # (or vice versa) falls back to the host-staged path
            if mesh is None:
                raise CheckpointCorruptError(
                    f"{path}: sharded restore failed: {e}") from e
    try:
        ffmodel.params = _host_staged_restore(
            ckptr, os.path.join(path, "params"), ffmodel.params)
        ffmodel.opt_state = _host_staged_restore(
            ckptr, os.path.join(path, "opt_state"), ffmodel.opt_state)
    except Exception as e:
        # a topology-changing restore that still fails must name the two
        # topologies and the way out, not surface a bare orbax/sharding
        # exception (ISSUE 5 satellite)
        import numpy as np

        saved_ndev = int(meta.get("n_devices")
                         or np.prod(meta.get("mesh_shape", [1]) or [1]))
        live_ndev = len(jax.devices())
        live_mesh = (list(ffmodel.strategy.mesh_shape)
                     if ffmodel.strategy is not None else "?")
        raise RuntimeError(
            f"{path}: restore failed while resharding a checkpoint saved "
            f"on {saved_ndev} device(s) (mesh "
            f"{meta.get('mesh_shape', '?')}) onto the live {live_ndev}-"
            f"device topology (mesh {live_mesh}): {type(e).__name__}: {e}. "
            "For a changed topology use resilience.elastic_restore("
            "ffmodel, path) — it re-runs the strategy search on the "
            "surviving devices and reshards host-staged — or --resume on "
            "the original topology.") from e
    return int(meta["step"])


# ------------------------------------------------------------- async manager
class CheckpointManager:
    """Background checkpoint writer with bounded-queue backpressure.

    ``save_async`` snapshots the live trees with device-side copies
    (donation-safe; the dispatch is async so the step loop keeps going) and
    enqueues them for the worker thread, which serializes, commits and
    prunes. The queue holds at most ``queue_depth`` pending snapshots —
    when the disk can't keep up, ``save_async`` blocks until a slot frees,
    bounding snapshot memory at ``queue_depth + 1`` copies of the model.

    Worker failures never kill training: they are recorded in ``errors``
    and surfaced as a warning; the previous committed checkpoint stays the
    restore target.
    """

    def __init__(self, ffmodel, directory: str, keep: int = 3,
                 queue_depth: int = 2):
        self.ffmodel = ffmodel
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.keep = max(int(keep), 1)
        self.saved = 0
        self.errors: List[str] = []
        self.last_committed_path: Optional[str] = latest_checkpoint(
            self.directory)
        self.last_committed_step: Optional[int] = None
        if self.last_committed_path is not None:
            try:
                self.last_committed_step = int(
                    read_meta(self.last_committed_path)["step"])
            except (OSError, ValueError, KeyError):
                self.last_committed_path = None
        self._q: Queue = Queue(maxsize=max(int(queue_depth), 1))
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="ckpt-writer")
        self._worker.start()

    # -- producer side -----------------------------------------------------
    def save_async(self, step: int,
                   train_state: Optional[Dict[str, Any]] = None) -> None:
        """Snapshot and enqueue; blocks only when the writer is
        ``queue_depth`` checkpoints behind (backpressure)."""
        snap_p = _device_snapshot(self.ffmodel.params)
        snap_o = _device_snapshot(self.ffmodel.opt_state)
        self._q.put((int(step), snap_p, snap_o, train_state))

    def save_sync(self, step: int,
                  train_state: Optional[Dict[str, Any]] = None
                  ) -> Optional[str]:
        """Drain pending async saves, then write ``step`` in the calling
        thread (the preemption-flush path: the checkpoint must be durable
        before the process exits the grace window). Skips the write when
        ``step`` is already the last committed one."""
        self.flush()
        if self.last_committed_step == int(step):
            return self.last_committed_path
        try:
            path = save_checkpoint(self.ffmodel, self.directory, step=step,
                                   train_state=train_state)
        except Exception as e:  # pragma: no cover - disk-full etc.
            self._note_error(step, e)
            return None
        self._committed(step, path)
        return path

    def flush(self) -> None:
        """Block until every enqueued snapshot is committed (or failed)."""
        self._q.join()

    def close(self) -> None:
        self.flush()
        self._q.put(None)
        self._worker.join(timeout=60.0)

    # -- worker side -------------------------------------------------------
    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            step, snap_p, snap_o, train_state = item
            try:
                path = save_checkpoint(self.ffmodel, self.directory,
                                       step=step, train_state=train_state,
                                       params=snap_p, opt_state=snap_o)
                self._committed(step, path)
            except Exception as e:
                self._note_error(step, e)
            finally:
                self._q.task_done()

    def _committed(self, step: int, path: str) -> None:
        self.saved += 1
        self.last_committed_step = int(step)
        self.last_committed_path = path
        prune_checkpoints(self.directory, self.keep)

    def _note_error(self, step: int, e: Exception) -> None:
        import warnings

        msg = f"checkpoint step {step} failed: {type(e).__name__}: {e}"
        self.errors.append(msg)
        warnings.warn(msg)
