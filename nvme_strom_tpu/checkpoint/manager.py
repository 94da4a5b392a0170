"""Step-numbered checkpointing of training pytrees via the direct engine.

Layout of one checkpoint (``<dir>/step_00000100/``):

    state-00000.safetensors   tensors owned by process 0
    state-00001.safetensors   … one file per writing process …
    meta.json                 step, process count, tensor→tile index

Every process writes ONLY the shard tiles its addressable devices hold
(the write-side mirror of the lazy loader's read-only-your-shard rule,
parallel/weights.py): bulk checkpoint bytes never cross hosts, matching the
reference's single-host DMA locality (SURVEY.md §5).  A device's shard IS
its tile — general N-d bounds in meta.json — so ANY sharding topology
(3-axis tp×pp×sp splits, cross-host column sharding, partial replication)
saves without host-side stitching, and restore reassembles arbitrary
target regions from intersecting tiles, so a checkpoint written under one
mesh restores under a different one.  Saves are atomic: the step
directory is staged under a dotted temp name and renamed into place
only after every payload byte is on disk, so a crashed save can never be
mistaken for a checkpoint (the failure-recovery story SURVEY.md §5 asks
for).  Restore places each region straight onto its devices with
``jax.make_array_from_callback`` — no host-side global tensor is ever
assembled.
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import time
from typing import Callable, Dict, Optional, Sequence, Union

import numpy as np

from nvme_strom_tpu.formats.safetensors import (
    SafetensorsFile,
    _np_dtype,
    tensor_checksums,
    write_safetensors_engine,
)
from nvme_strom_tpu.io.engine import StromEngine, wait_exact
from nvme_strom_tpu.io.faults import crash_point
from nvme_strom_tpu.io.plan import plan_and_submit
from nvme_strom_tpu.utils.checksum import VerifyPolicy
from nvme_strom_tpu.utils.config import EngineConfig

_STEP_RE = re.compile(r"^step_(\d{8})$")
_TMP_RE = re.compile(r"^\.tmp_step_(\d{8})$")
_log = logging.getLogger(__name__)


def _gc_min_age() -> float:
    """The live-save age gate (``STROM_CKPT_GC_AGE_S``, default 3600s)
    shared by the startup GC and ``strom-scrub --gc`` — one parse so
    the two sweepers can never disagree about what counts as debris."""
    try:
        return float(os.environ.get("STROM_CKPT_GC_AGE_S", 3600))
    except ValueError:
        return 3600.0


_KVMAN_SUFFIX = ".kvman.json"
# hostcache warmup-hint sidecars (io/warmup.py) ride the exact same
# orphan rules: same age gate, same sweeper, a second suffix
_WARMHINT_SUFFIX = ".warmhints.json"
# drain & handoff bundles (io/handoff.py): a bundle whose anchor file
# is gone can never validate, so it is debris under the same gate
_HANDOFF_SUFFIX = ".handoff.json"
_SIDECAR_SUFFIXES = (_KVMAN_SUFFIX, _WARMHINT_SUFFIX,
                     _HANDOFF_SUFFIX)


def _is_orphan_sidecar(path: str, name: str, suffixes) -> bool:
    for suf in suffixes:
        if name.endswith(suf):
            return not os.path.exists(path[:-len(suf)])
    return False


def find_orphan_manifests(root: str, recursive: bool = True,
                          suffixes=_SIDECAR_SUFFIXES) -> list:
    """Sidecar manifests whose base file is gone — a deleted or
    crash-torn store's debris.  Covers the serving KV prefix-store
    manifest (``.kvman.json``, models/kv_offload.py) and the hostcache
    warmup-hint list (``.warmhints.json``, io/warmup.py): a stale hint
    file would mis-warm the next boot, so it follows the same rules.
    ``recursive=False`` scans only ``root`` itself (the manager's
    startup scope: cheap on huge checkpoint trees; ``strom-scrub``
    applies the same missing-base-file verdict inline during its own
    full walk, and both sweepers remove via
    :func:`sweep_orphan_manifests` so the age-gate semantics can never
    diverge)."""
    out = []
    if recursive:
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if not _TMP_RE.match(d)]
            for name in filenames:
                p = os.path.join(dirpath, name)
                if _is_orphan_sidecar(p, name, suffixes):
                    out.append(p)
    else:
        try:
            names = os.listdir(root)
        except OSError:
            return []
        for name in names:
            p = os.path.join(root, name)
            if _is_orphan_sidecar(p, name, suffixes):
                out.append(p)
    return sorted(out)


def sweep_orphan_manifests(paths, min_age: float) -> list:
    """Unlink orphaned manifests older than ``min_age`` (the same
    live-save gate as the staging-dir GC: a store racing a
    delete/recreate cycle is never swept out from under its process);
    returns the paths actually removed.  Races (concurrent removal,
    permissions) skip the entry — debris is harmless, a false removal
    is not."""
    removed = []
    now = time.time()
    for p in paths:
        try:
            if now - os.path.getmtime(p) < min_age:
                continue
            os.unlink(p)
        except OSError:
            continue
        removed.append(p)
    return removed


def _newest_mtime(path: str) -> float:
    """Newest mtime across a staging dir and its immediate entries.
    The dir mtime alone moves only on entry creation/rename — a save
    that has been engine-writing into one large tile file for a while
    bumps the FILE's mtime on every write, not the dir's, and must not
    look cold to the GC age gate."""
    newest = os.path.getmtime(path)
    try:
        with os.scandir(path) as it:
            for ent in it:
                try:
                    newest = max(newest, ent.stat().st_mtime)
                except OSError:
                    continue
    except OSError:
        pass
    return newest


class TargetMismatchError(ValueError):
    """The restore target's schema disagrees with the checkpoint (wrong
    shape, renamed/missing tensor): a code bug, never checkpoint damage
    — restore-fallback must not step past it to an older checkpoint
    that would fail (or, worse, silently fit) the same wrong target."""


# --------------------------------------------------------------------------
# pytree <-> flat {name: leaf}
# --------------------------------------------------------------------------

def _key_to_str(k) -> str:
    import jax.tree_util as jtu

    if isinstance(k, jtu.DictKey):
        return str(k.key)
    if isinstance(k, jtu.SequenceKey):
        return str(k.idx)
    if isinstance(k, jtu.GetAttrKey):
        return str(k.name)
    if isinstance(k, jtu.FlattenedIndexKey):
        return str(k.key)
    return str(k)


def flatten_with_names(tree) -> tuple[Dict[str, object], object]:
    """Pytree → ({path-name: leaf}, treedef).  Names join key-path entries
    with '|' (tensor names may themselves contain '.' and '/')."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    named = {}
    for path, leaf in leaves:
        name = "|".join(_key_to_str(k) for k in path) or "_root"
        if name in named:
            raise ValueError(f"duplicate flattened name {name!r}")
        named[name] = leaf
    return named, treedef


def unflatten_from_names(treedef, named: Dict[str, object], order):
    import jax

    return jax.tree_util.tree_unflatten(
        treedef, [named[n] for n in order])


# --------------------------------------------------------------------------

def _norm_index(idx, shape) -> tuple:
    """Device index (tuple of slices) → concrete ((a0,b0), (a1,b1), …)
    bounds over ``shape``.  Scalars normalize to ()."""
    idx = tuple(idx)
    out = []
    for s, d in zip(idx, shape):
        out.append((0 if s.start is None else int(s.start),
                    d if s.stop is None else int(s.stop)))
    # devices_indices_map may omit trailing fully-covered dims
    for d in shape[len(idx):]:
        out.append((0, d))
    return tuple(out)


def _tiles(arr) -> Dict[tuple, list]:
    """Distinct shard tiles of a jax.Array: {bounds: [devices]} where
    bounds is a per-dim (start, stop) tuple — ANY sharding topology
    (row, column, 3-axis, partial-replication) reduces to its set of
    distinct tiles, each written verbatim by one owning process."""
    shape = arr.shape
    tiles: Dict[tuple, list] = {}
    for dev, idx in arr.sharding.devices_indices_map(shape).items():
        tiles.setdefault(_norm_index(idx, shape), []).append(dev)
    return tiles


def _tile_key(name: str, bounds: tuple, shape: tuple) -> str:
    """Safetensors entry name for one tile; the untiled (full) tensor
    keeps its plain name."""
    if bounds == tuple((0, d) for d in shape):
        return name
    return name + "@t" + "x".join(f"{a}-{b}" for a, b in bounds)


class CheckpointManager:
    """Save/restore step-numbered training-state checkpoints.

    ``state`` can be any pytree of jax/numpy arrays and Python scalars
    (params dicts, optax optimizer states, step counters).  Restore takes a
    ``target`` pytree of the same structure — its leaves supply shapes,
    dtypes, and (for jax.Array leaves) the shardings to restore under, so a
    checkpoint written under one mesh can be read back under another.
    """

    def __init__(self, directory: Union[str, os.PathLike],
                 max_to_keep: Optional[int] = 3,
                 engine: Optional[StromEngine] = None):
        self.directory = str(directory)
        self.max_to_keep = max_to_keep
        self._engine = engine
        self._executor = None      # lazy, one IO thread (save_async)
        self._pending = None
        #: step the last successful restore() actually read — differs
        #: from the requested step when restore-fallback engaged
        self.last_restore_step: Optional[int] = None
        os.makedirs(self.directory, exist_ok=True)
        #: dotted temp dirs from crashed saves removed at startup
        self.tmp_gc: list[str] = []
        #: orphaned .kvman.json manifests (page file gone) removed
        self.manifest_gc: list[str] = []
        if os.environ.get("STROM_CKPT_GC", "1") != "0":
            self._gc_tmp_dirs()
            self._gc_orphan_manifests()

    def _gc_tmp_dirs(self) -> None:
        """Startup GC: remove orphaned ``.tmp_step_*`` staging dirs left
        by crashed saves (docs/RESILIENCE.md).  A crash anywhere before
        the atomic rename leaves the dotted dir behind — invisible to
        ``all_steps`` (restore already falls back past it) but
        accumulating payload-sized garbage on the NVMe namespace.  This
        process has no save in flight yet, and multi-host runs construct
        their managers at the same startup point — but a DIFFERENT
        process (an eval job restoring from a live training dir) may be
        mid-save, so only dirs whose newest mtime (the dir or any file
        inside it — a long engine write bumps the tile file, not the
        dir) is older than ``STROM_CKPT_GC_AGE_S`` (default 3600) are
        debris: a live staging dir keeps moving, a crashed one froze
        at the crash.  ``STROM_CKPT_GC=0``
        opts out entirely for post-mortem inspection of a torn save;
        ``strom-scrub --gc`` honors the same age gate (``--force``
        overrides it)."""
        min_age = _gc_min_age()
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        now = time.time()
        for name in names:
            if not _TMP_RE.match(name):
                continue
            path = os.path.join(self.directory, name)
            try:
                if (not os.path.isdir(path)
                        or now - _newest_mtime(path) < min_age):
                    continue
            except OSError:
                continue    # racing rename/removal: not ours to touch
            shutil.rmtree(path, ignore_errors=True)
            if os.path.exists(path):
                # rmtree swallowed an error (foreign-uid file,
                # immutable flag): the debris is still there — say so
                # instead of recording a removal that didn't happen
                _log.warning(
                    "could not remove orphaned checkpoint staging dir "
                    "%s (permission?); remove it manually or with "
                    "strom-scrub --gc", path)
                continue
            self.tmp_gc.append(path)
            _log.warning(
                "removed orphaned checkpoint staging dir %s "
                "(crashed save; the previous intact step is unaffected)",
                path)

    def _gc_orphan_manifests(self) -> None:
        """Startup GC, KV-store half: a serving PrefixStore
        (models/kv_offload.py) colocated with the checkpoint dir leaves
        a ``.kvman.json`` manifest beside its page file; deleting or
        crash-tearing the page file strands the manifest — harmless but
        accumulating, and it makes ``strom-scrub`` report a vanished
        store forever.  Top-level scope only, like ``_gc_tmp_dirs``
        (stores live beside the step dirs, and a full-tree walk at
        every manager construction is a stat storm on big trees —
        ``strom-scrub --gc`` covers nested debris)."""
        orphans = find_orphan_manifests(self.directory, recursive=False)
        self.manifest_gc = sweep_orphan_manifests(orphans,
                                                  _gc_min_age())
        for path in self.manifest_gc:
            _log.warning(
                "removed orphaned kv-store manifest %s (its page "
                "file is gone; the store rebuilds on first use)",
                path)

    # -- introspection -----------------------------------------------------

    def all_steps(self) -> list[int]:
        steps = []
        for name in os.listdir(self.directory):
            m = _STEP_RE.match(name)
            if not m:
                continue
            # A step only counts if its meta.json parses AND its format
            # is readable — a torn write from a crashed save must not
            # shadow older intact checkpoints, and latest_step() must
            # never steer restore() into a format it cannot read.
            try:
                with open(os.path.join(self.directory, name,
                                       "meta.json")) as f:
                    if json.load(f).get("format") != 2:
                        continue
            except (OSError, json.JSONDecodeError):
                continue
            steps.append(int(m.group(1)))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    # -- save --------------------------------------------------------------

    def save(self, step: int, state, force: bool = False) -> str:
        """Write ``state`` as checkpoint ``step``; returns the final path.

        Each process writes its own ``state-{proc}.safetensors`` with the
        shard tiles it owns (owner = lowest process index holding the
        tile); process 0 writes the tile index in meta.json.  The temp
        directory is renamed in only when everything is durable.
        """
        self.wait_pending()
        return self._write(step, *self._snapshot(step, state, force))

    def save_async(self, step: int, state, force: bool = False):
        """Checkpoint without blocking the train loop on the NVMe write.

        The device→host snapshot happens NOW (synchronously — the tiles
        are plain numpy copies afterwards, so later donation/mutation of
        ``state`` by the train loop cannot corrupt the checkpoint); the
        slow half — engine writes, fsyncs, the atomic rename — runs on a
        background thread.  Returns a ``concurrent.futures.Future``
        resolving to the final path.  At most one save is in flight:
        a second save_async (or any save/restore) first waits for the
        previous one and re-raises its error if it failed.

        Multi-host (round-2 verdict #7): the background half is
        COLLECTIVE-FREE — cross-host jax collectives on a side thread
        would race the train loop's own collectives (two hosts, two
        dispatch orders → mutual block).  Coordination rides the shared
        checkpoint filesystem instead: every host stages into the same
        temp dir (no entry barrier — the snapshot's consistency comes
        from all hosts calling save_async at the same train-step point,
        which the step's own collectives already synchronize), writes
        its tiles, then a fsync'd ``done-{proc}`` marker; host 0's
        background thread polls for all markers (STROM_CKPT_WAIT_S,
        default 600) and only then writes the manifest and renames the
        step in.  A crash anywhere before the rename leaves a dotted
        temp dir that ``all_steps`` never reports — restore picks the
        previous step.  Non-zero hosts' futures resolve only once the
        rename is VISIBLE to them (so wait_pending/restore can never
        read past an in-flight save on any host); a dead host 0
        surfaces as a TimeoutError on every peer.
        """
        import atexit
        import concurrent.futures

        self.wait_pending()
        args = self._snapshot(step, state, force, barrier=False)
        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="strom-ckpt")
            # a failed FINAL save must not vanish when the process exits
            # without calling wait_pending — surface it at teardown
            atexit.register(self.wait_pending)
        self._pending = self._executor.submit(
            self._write_collective_free, step, *args)
        return self._pending

    def wait_pending(self) -> None:
        """Block until an in-flight save_async (if any) completed;
        re-raises its failure.  restore() calls this so a restore can
        never read past a checkpoint that is still being written."""
        if self._pending is not None:
            f, self._pending = self._pending, None
            f.result()

    def _snapshot(self, step: int, state, force: bool,
                  barrier: bool = True):
        """Phase 1 (synchronous): validate, stage the temp dir, snapshot
        every owned tile to host numpy.  Cheap relative to the NVMe
        write (HBM→host runs at link speed) and MUST be synchronous:
        the snapshot is the checkpoint's consistency point.

        ``barrier=False`` (the async path): no collectives — host 0
        clears a stale temp dir from a crashed earlier attempt and every
        host ``makedirs(exist_ok=True)``.  The no-barrier race (a host
        so far ahead its background write lands before host 0's cleanup)
        fails loudly — ENOENT on the deleted file or a marker-wait
        timeout — never silently; in practice the hosts enter here at
        the same train-step point."""
        import jax

        proc = jax.process_index()
        final = self.step_dir(step)
        if os.path.exists(final):
            if not force:
                raise FileExistsError(f"checkpoint step {step} exists")
            if proc == 0:  # single deleter on a shared filesystem
                shutil.rmtree(final)
        tmp = os.path.join(self.directory, f".tmp_step_{step:08d}")
        if proc == 0:
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            # exist_ok on the barrier-free path: a peer's makedirs can
            # land between the exists() check and ours
            os.makedirs(tmp, exist_ok=not barrier)
        if barrier:
            self._sync()
        else:
            os.makedirs(tmp, exist_ok=True)

        named, _ = flatten_with_names(state)
        mine: Dict[str, np.ndarray] = {}   # entries this process writes
        index: Dict[str, dict] = {}        # global tile index (proc 0 view)
        for name, leaf in named.items():
            if leaf is None:
                continue
            tiles = self._leaf_tiles(leaf)
            dt = (leaf.dtype if hasattr(leaf, "dtype")
                  else np.asarray(leaf).dtype)
            entry = {"shape": list(np.shape(leaf)),
                     "dtype": str(dt),
                     "scalar": not isinstance(
                         leaf, (jax.Array, np.ndarray)),
                     "tiles": []}
            for bounds, owner, local in tiles:
                fname = f"state-{owner:05d}.safetensors"
                entry["tiles"].append(
                    {"file": fname, "idx": [list(b) for b in bounds]})
                if owner == proc and local is not None:
                    mine[_tile_key(name, bounds, np.shape(leaf))] = local
            index[name] = entry
        return tmp, final, mine, index

    def _write(self, step: int, tmp: str, final: str,
               mine: Dict[str, np.ndarray], index: Dict[str, dict]) -> str:
        """Phase 2 (threadable): engine writes, meta, fsync, rename."""
        import jax

        proc = jax.process_index()
        eng, own = self._get_engine()
        t0 = time.monotonic()
        try:
            write_safetensors_engine(
                os.path.join(tmp, f"state-{proc:05d}.safetensors"), mine,
                eng, metadata={"step": step, "process": proc})
        finally:
            if own:
                eng.close_all()
        crash_point("ckpt.tiles")   # torn-save window: data, no commit
        t1 = time.monotonic()

        if proc == 0:
            self._write_meta(tmp, step, index)
        crash_point("ckpt.meta")    # manifest staged, rename pending
        self._sync()  # all payloads durable before the rename
        crash_point("ckpt.rename")  # the instant before the commit
        if proc == 0:
            self._publish(tmp, final)
        self._sync()
        # phase telemetry: tiles = engine writes + the data file's own
        # fdatasync; commit = manifest fsync + durable rename — PLUS,
        # in a multi-host save, the _sync() barrier waits (a straggler
        # peer's tile time shows up here, not in tiles_s).  The
        # breakdown lets a reader tell durability cost from bandwidth;
        # at small payloads the device FLUSHes dominate and amortize
        # away at real checkpoint sizes.
        self.last_save_phases = {
            "tiles_s": round(t1 - t0, 4),
            "commit_s": round(time.monotonic() - t1, 4),
        }
        if proc == 0:
            self._prune()
        return final

    def _write_meta(self, tmp: str, step: int,
                    index: Dict[str, dict]) -> None:
        """The manifest — the checkpoint's commit record."""
        import jax

        meta = {"format": 2, "step": step, "time": time.time(),
                "process_count": jax.process_count(), "tensors": index}
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())

    def _publish(self, tmp: str, final: str) -> None:
        """Atomic, durable rename of the staged dir into place."""
        os.replace(tmp, final)
        # fsync the parent so the rename itself is durable — without it
        # a crash can publish the dir name before meta.json's blocks.
        dfd = os.open(self.directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def _prune(self) -> None:
        if self.max_to_keep:
            for old in self.all_steps()[:-self.max_to_keep]:
                shutil.rmtree(self.step_dir(old), ignore_errors=True)

    def _write_collective_free(self, step: int, tmp: str, final: str,
                               mine: Dict[str, np.ndarray],
                               index: Dict[str, dict]) -> str:
        """Background half of save_async: no jax collectives anywhere.
        Data + marker, then (host 0 only) marker-wait → manifest →
        rename.  Split into :meth:`_write_data_and_marker` and
        :meth:`_finalize` so the crash window between them is directly
        testable: anything that dies after data but before finalize
        leaves only the dotted temp dir, and restore picks the previous
        step."""
        import jax

        self._write_data_and_marker(step, tmp, mine)
        if jax.process_index() != 0:
            # resolve only once host 0's rename is visible — otherwise
            # wait_pending()/restore() on this host could read PAST an
            # in-flight save and pick a different step than host 0
            # (divergent state, garbage collectives, no error)
            self._await_commit(step, tmp, final)
            return final
        return self._finalize(step, tmp, final, index)

    def _await_commit(self, step: int, tmp: str, final: str) -> None:
        """Non-zero hosts: poll for host 0's commit.  Committed ⇔ the
        final dir exists AND the temp dir is gone (a force-overwrite's
        STALE final dir can't satisfy that — this host's own marker
        proves tmp existed after staging, and only the rename removes
        it).  A dead host 0 turns into a loud TimeoutError here."""
        deadline = time.monotonic() + float(
            os.environ.get("STROM_CKPT_WAIT_S", 600))
        while not (os.path.isdir(final) and not os.path.exists(tmp)):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"checkpoint step {step}: host 0 never published "
                    f"{os.path.basename(final)} (STROM_CKPT_WAIT_S)")
            time.sleep(0.1)

    def _write_data_and_marker(self, step: int, tmp: str,
                               mine: Dict[str, np.ndarray]) -> None:
        """This host's tiles → engine writes; then a durable done
        marker (written only after the data file's own fsync)."""
        import jax

        proc = jax.process_index()
        eng, own = self._get_engine()
        fname = os.path.join(tmp, f"state-{proc:05d}.safetensors")
        try:
            write_safetensors_engine(
                fname, mine, eng, metadata={"step": step,
                                            "process": proc})
        finally:
            if own:
                eng.close_all()
        crash_point("ckpt.tiles")   # data durable, marker not yet cut
        marker = os.path.join(tmp, f"done-{proc:05d}.json")
        with open(marker, "w") as f:
            json.dump({"step": step, "process": proc,
                       "nbytes": os.path.getsize(fname)}, f)
            f.flush()
            os.fsync(f.fileno())
        crash_point("ckpt.marker")  # marker cut, commit still pending

    def _finalize(self, step: int, tmp: str, final: str,
                  index: Dict[str, dict]) -> str:
        """Host 0: wait for every host's marker on the shared
        filesystem, write the manifest, unlink the markers, rename the
        step in (durably).  The manifest is the commit point — a step
        without meta.json does not exist to ``all_steps``."""
        import jax

        n = jax.process_count()
        deadline = time.monotonic() + float(
            os.environ.get("STROM_CKPT_WAIT_S", 600))
        markers = [os.path.join(tmp, f"done-{p:05d}.json")
                   for p in range(n)]
        while True:
            missing = [m for m in markers if not os.path.exists(m)]
            if not missing:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"checkpoint step {step}: hosts "
                    f"{[os.path.basename(m) for m in missing]} never "
                    f"wrote their done markers (STROM_CKPT_WAIT_S)")
            time.sleep(0.1)
        self._write_meta(tmp, step, index)
        crash_point("ckpt.meta")    # manifest staged, rename pending
        for m in markers:
            os.unlink(m)
        crash_point("ckpt.rename")  # the instant before the commit
        self._publish(tmp, final)
        self._prune()
        return final

    def _leaf_tiles(self, leaf):
        """→ [(bounds, owner_proc, local_data_or_None), ...].

        One entry per distinct shard tile; a device's shard IS its tile,
        so no host-side stitching is ever needed and every sharding
        topology (any axis count, partial replication, cross-host column
        splits) saves the same way.  Owner = lowest process index holding
        the tile; ``local_data`` is None when another process owns it.
        For non-jax leaves and single-process runs this is one full tile
        owned by process 0.
        """
        import jax

        if not isinstance(leaf, jax.Array):
            arr = np.asarray(leaf)
            bounds = tuple((0, d) for d in arr.shape)
            return [(bounds, 0, arr)]
        shape = leaf.shape
        local = {}
        for shard in leaf.addressable_shards:
            local[_norm_index(shard.index, shape)] = shard.data
        out = []
        for bounds, devs in sorted(_tiles(leaf).items()):
            owner = min(d.process_index for d in devs)
            data = None
            if owner == jax.process_index():
                if bounds not in local:
                    raise ValueError(
                        f"tile owner holds no addressable shard for "
                        f"{bounds}")
                data = np.asarray(jax.device_get(local[bounds]))
            out.append((bounds, owner, data))
        return out

    # -- restore -----------------------------------------------------------

    #: exception classes that mean "this checkpoint is damaged" (torn
    #: manifest, missing/truncated tile file, under-covered region) —
    #: the set restore-fallback steps past.  Target-schema errors
    #: (TargetMismatchError, KeyError from a tensor the target has but
    #: the manifest lacks) are NOT damage: they are code bugs that every
    #: candidate would reproduce, so they stay fatal on the first step.
    _DAMAGE = (OSError, ValueError, json.JSONDecodeError)

    def restore(self, target, step: Optional[int] = None,
                shardings: Union[Dict, Callable, None] = None,
                fallback: bool = True, ici_mesh=None):
        """Read checkpoint ``step`` (default: latest) into ``target``'s
        structure.  Leaf placement: ``shardings`` (dict name→Sharding or
        fn(name, shape)→Sharding) wins; else a jax.Array target leaf's own
        sharding; else the array stays a host-resident numpy array.

        Read-once/scatter mode (``STROM_ICI_SCATTER=1``, docs/PERF.md
        §7): each host NVMe-reads only its 1/N contiguous byte share of
        the step's payload files (at ``restore`` class, through the
        ordinary planner/scheduler/breaker stack) and the mesh
        all-gathers the shares over ICI; every tile read below is then
        served from the gathered bytes — bit-identical by construction,
        since the shares cover every payload byte exactly once.
        ``ici_mesh`` pins the exchange mesh (1-axis ``("hosts",)``;
        default ``parallel.mesh.exchange_mesh``).  Any scatter failure
        — breaker open, exchange error, single-host mesh — browns out
        to the plain read-all path (counted ``ici_fallbacks``), never
        to a restore error.  Mode off (the default) touches zero code
        paths.

        ``fallback`` (docs/RESILIENCE.md): when the chosen step turns
        out damaged — manifest unreadable, a tile file missing or
        truncated, a region under-covered — fall back to the next-older
        intact step instead of killing the run on a checkpoint that no
        retry can repair.  Every step skipped is logged loudly, counted
        (``StromStats.restore_fallbacks``), and traced; the step
        actually restored lands in ``self.last_restore_step``.  Only
        when NO candidate restores does the last error surface (the
        original exception when a single candidate existed).  Pass
        ``fallback=False`` to fail fast on exactly the requested step.
        """
        self.wait_pending()  # never read past an in-flight async save

        steps = self.all_steps()
        if step is None:
            if not steps:
                raise FileNotFoundError(
                    f"no checkpoints under {self.directory}")
            candidates = steps[::-1]
        else:
            if step not in steps and not os.path.isdir(self.step_dir(step)):
                # a step that never existed is a caller bug (typo),
                # not damage — silently restoring an older step here
                # would resume training from the wrong state
                raise FileNotFoundError(
                    f"checkpoint step {step} does not exist under "
                    f"{self.directory} (have {steps})")
            # the pinned step first (even if its manifest no longer
            # parses — the failure itself is the fallback trigger),
            # then every intact older step
            candidates = [step] + [s for s in steps[::-1] if s < step]
        if not fallback:
            candidates = candidates[:1]

        # flatten ONCE, before any candidate: a malformed target
        # (duplicate flattened names) is a code bug and must raise here,
        # not be retried against every checkpoint as "damage"
        named_t, treedef = flatten_with_names(target)

        # read-side integrity gate (STROM_VERIFY, utils/checksum.py):
        # one policy per restore call so the mode cannot flip between
        # candidate steps.  A checksum mismatch is _DAMAGE (ChecksumError
        # is an OSError): retried once at the tile read, then this very
        # fallback loop steps to the previous intact checkpoint.
        self._verify = VerifyPolicy()

        eng, own = self._get_engine()
        try:
            for i, s in enumerate(candidates):
                try:
                    eng_s = self._scatter_engine(eng, s, ici_mesh)
                    out = self._restore_step(eng_s or eng, named_t,
                                             treedef, s, shardings)
                except self._DAMAGE as e:
                    if isinstance(e, TargetMismatchError):
                        raise       # schema bug, not damage
                    if i + 1 >= len(candidates):
                        raise
                    eng.stats.add(restore_fallbacks=1)
                    tracer = getattr(eng, "tracer", None)
                    if tracer is not None and tracer.enabled:
                        now = time.monotonic_ns()
                        tracer.add_span(
                            "strom.ckpt.restore_fallback", now, now,
                            category="strom.resilient", step=s,
                            next_step=candidates[i + 1],
                            error=f"{type(e).__name__}: {e}")
                    _log.warning(
                        "checkpoint step %d is damaged (%s: %s); "
                        "falling back to step %d", s, type(e).__name__,
                        e, candidates[i + 1])
                else:
                    self.last_restore_step = s
                    return out
        finally:
            if own:
                eng.close_all()

    def _scatter_engine(self, eng, step: int, ici_mesh=None):
        """Read-once/scatter front-end over ``eng`` for candidate
        ``step``, or None for the plain read-all path (mode off, or any
        scatter-build failure — counted ``ici_fallbacks`` — because a
        scatter brown-out must never become a restore error).  The
        manifest derives deterministically from the step directory
        (checkpoint/scatter.py), so every host partitions identically
        without coordination traffic."""
        from nvme_strom_tpu.ops.ici import (
            ici_scatter_enabled, ici_unit_bytes, scatter_engine)
        if not ici_scatter_enabled():
            return None
        try:
            from nvme_strom_tpu.checkpoint.scatter import (
                build_restore_manifest)
            from nvme_strom_tpu.ops.ici import ici_hosts
            from nvme_strom_tpu.parallel.mesh import exchange_mesh
            mesh = (ici_mesh if ici_mesh is not None
                    else exchange_mesh(ici_hosts()))
            man = build_restore_manifest(
                self.step_dir(step), int(mesh.shape["hosts"]),
                ici_unit_bytes())
            return scatter_engine(eng, list(man.paths), mesh=mesh,
                                  klass="restore", manifest=man.shares)
        except Exception as e:
            _log.warning(
                "ici scatter disabled for step %d: %s: %s (falling "
                "back to local full reads)", step, type(e).__name__, e)
            eng.stats.add(ici_fallbacks=1)
            return None

    def _restore_step(self, eng, named_t, treedef, step: int,
                      shardings: Union[Dict, Callable, None]):
        """One restore attempt against exactly checkpoint ``step``."""
        d = self.step_dir(step)
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("format") != 2:
            raise ValueError(
                f"checkpoint format {meta.get('format')} unsupported "
                "(this reader is format 2, the general tile index; "
                "re-save from the run that wrote it)")

        files: Dict[str, SafetensorsFile] = {}
        out: Dict[str, object] = {}
        for name, tleaf in named_t.items():
            if tleaf is None:
                out[name] = None
                continue
            info = meta["tensors"].get(name)
            if info is None:
                raise KeyError(
                    f"checkpoint step {step} lacks tensor {name!r}")
            out[name] = self._restore_leaf(
                eng, d, files, name, info, tleaf, shardings)
        return unflatten_from_names(treedef, out, list(named_t))

    def _restore_leaf(self, eng, cdir, files, name, info, tleaf, shardings):
        import jax
        import jax.numpy as jnp

        shape = tuple(info["shape"])
        np_dt = _np_dtype(info["dtype"])
        t_shape = tuple(np.shape(tleaf))
        if t_shape != shape:
            raise TargetMismatchError(
                f"{name}: checkpoint shape {shape} != "
                f"target shape {t_shape}")

        sh = None
        if shardings is not None:
            try:
                sh = (shardings.get(name) if isinstance(shardings, dict)
                      else shardings(name, shape))
            except Exception as e:
                # a user shardings callable blowing up is a code bug —
                # must not be classified as checkpoint damage and walked
                # past to older steps (it would fail them all identically)
                raise TargetMismatchError(
                    f"shardings callback failed for {name!r}: "
                    f"{type(e).__name__}: {e}") from e
        if sh is None and isinstance(tleaf, jax.Array) \
                and hasattr(tleaf, "sharding"):
            sh = tleaf.sharding

        read_region = self._make_region_reader(eng, cdir, files, name,
                                               info, shape, np_dt)
        if info.get("scalar"):
            val = read_region(()).reshape(())[()]
            if isinstance(tleaf, np.ndarray):
                return np.asarray(val, dtype=tleaf.dtype).reshape(())
            if isinstance(tleaf, jax.Array):
                return jnp.asarray(val, dtype=tleaf.dtype)
            return type(tleaf)(val)  # python int/float/bool, np scalars
        if sh is None:
            host = read_region(tuple((0, d) for d in shape))
            if isinstance(tleaf, np.ndarray):
                return host.astype(tleaf.dtype, copy=False)
            return jnp.asarray(host, dtype=getattr(tleaf, "dtype", None))

        region_cache: Dict = {}  # partially-replicated shardings ask for
        # the same region once per replica: read/assemble it ONCE.

        def cb(index):
            bounds = _norm_index(index, shape)
            got = region_cache.get(bounds)
            if got is None:
                got = region_cache[bounds] = read_region(bounds)
            return got

        arr = jax.make_array_from_callback(shape, sh, cb)
        tdt = getattr(tleaf, "dtype", None)
        if tdt is not None and arr.dtype != tdt:
            arr = jax.jit(lambda x: x.astype(tdt),
                          out_shardings=sh)(arr)
        return arr

    def _make_region_reader(self, eng, cdir, files, name, info, shape,
                            np_dt):
        """Returns read_region(bounds) -> np array of that region of the
        global tensor, assembled from whichever stored tiles intersect it
        (general N-d: restore under ANY target mesh/sharding, including
        one the checkpoint was not written under).  Whole stored tiles
        are read once via direct engine reads and cached for the leaf."""

        tiles = [(tuple(tuple(b) for b in t["idx"]), t["file"])
                 for t in info["tiles"]]
        tile_cache: Dict = {}
        policy = getattr(self, "_verify", None)
        if policy is None:
            policy = VerifyPolicy("off")
        crc_cache: Dict[str, Dict[str, int]] = {}   # fname → stamps

        def get_sf(fname):
            sf = files.get(fname)
            if sf is None:
                sf = SafetensorsFile(os.path.join(cdir, fname))
                files[fname] = sf
            return sf

        def verify_tile(sf, fname, tkey, t, flat) -> np.ndarray:
            """Whole-tile CRC32C check against the write-time stamp,
            via the shared retry-once protocol (utils/checksum.py): a
            mismatch re-reads the tile ONCE (transient in-flight
            corruption heals, counted), and a second mismatch raises
            ChecksumError — an OSError, i.e. _DAMAGE, so restore steps
            back to the previous intact checkpoint."""
            stamps = crc_cache.get(fname)
            if stamps is None:
                stamps = crc_cache[fname] = tensor_checksums(sf)
            expected = stamps.get(tkey)
            if expected is None or not policy.want():
                return flat         # unstamped / not sampled this time
            from nvme_strom_tpu.io.hostcache import spoil_path
            return policy.check_with_reread(
                flat, expected,
                lambda: self._engine_read(eng, sf.path, t["offset"],
                                          t["nbytes"]),
                eng.stats, where=f"tile {tkey} of {sf.path}",
                spoil=lambda: spoil_path(sf.path, t["offset"],
                                         t["nbytes"], eng.stats))

        def read_tile_rows(bounds, fname, a, b):
            """Rows [a, b) (tile-local, leading axis) of a stored tile —
            a contiguous byte range, so a cross-mesh restore that needs a
            sliver of a tile reads only those rows from NVMe, not the
            whole tile (parity with the old row-span sub-range reads).
            Under ``STROM_VERIFY`` a whole-tile read is checked against
            its write-time stamp; ``full`` mode widens partial-row
            requests to the whole tile (cached — each tile reads and
            verifies once) so every consumed byte is covered."""
            tshape = tuple(hi - lo for lo, hi in bounds)
            rows_total = tshape[0] if tshape else 1
            key = (bounds, a, b)
            got = tile_cache.get(key)
            if got is not None:
                return got
            whole = tile_cache.get((bounds, 0, rows_total))
            if whole is not None:
                return whole[a:b] if tshape else whole
            sf = get_sf(fname)
            tkey = _tile_key(name, bounds, shape)
            t = sf.tensors[tkey]
            if (policy.mode == "full" and tshape
                    and (a, b) != (0, rows_total)):
                # widen a partial-row request to the whole tile ONLY
                # when a stamp exists to check it against — an
                # unstamped (pre-integrity) tile keeps the sliver read
                stamps = crc_cache.get(fname)
                if stamps is None:
                    stamps = crc_cache[fname] = tensor_checksums(sf)
                if stamps.get(tkey) is not None:
                    whole = read_tile_rows(bounds, fname, 0, rows_total)
                    return whole[a:b]
            if not tshape:  # scalar tile
                flat = self._engine_read(eng, sf.path, t["offset"],
                                         t["nbytes"])
                if policy.enabled:
                    flat = verify_tile(sf, fname, tkey, t, flat)
                got = flat.view(np_dt).reshape(())
            else:
                row_bytes = (np_dt.itemsize *
                             int(np.prod(tshape[1:], dtype=np.int64)))
                flat = self._engine_read(eng, sf.path,
                                         t["offset"] + a * row_bytes,
                                         (b - a) * row_bytes)
                if policy.enabled and (a, b) == (0, rows_total):
                    flat = verify_tile(sf, fname, tkey, t, flat)
                got = flat.view(np_dt).reshape((b - a,) + tshape[1:])
            tile_cache[key] = got
            return got

        def read_region(bounds):
            if not shape:  # scalar: the single () tile
                return read_tile_rows((), tiles[0][1], 0, 1)
            rshape = tuple(b - a for a, b in bounds)
            if 0 in rshape:
                return np.empty(rshape, dtype=np_dt)
            out = None
            covered = 0
            for tb, fname in tiles:
                lo = tuple(max(a, ta) for (a, _), (ta, _) in
                           zip(bounds, tb))
                hi = tuple(min(b, tb_) for (_, b), (_, tb_) in
                           zip(bounds, tb))
                if any(l >= h for l, h in zip(lo, hi)):
                    continue
                rows = read_tile_rows(tb, fname, lo[0] - tb[0][0],
                                      hi[0] - tb[0][0])
                if tb == bounds:  # exact tile: the same-mesh fast path
                    return rows
                src = (slice(None),) + tuple(
                    slice(l - ta, h - ta) for l, h, (ta, _) in
                    zip(lo[1:], hi[1:], tb[1:]))
                dst = tuple(slice(l - a, h - a) for l, h, (a, _) in
                            zip(lo, hi, bounds))
                if out is None:
                    out = np.empty(rshape, dtype=np_dt)
                out[dst] = rows[src]
                covered += int(np.prod(
                    [h - l for l, h in zip(lo, hi)], dtype=np.int64))
            want = int(np.prod(rshape, dtype=np.int64))
            if out is None or covered < want:
                raise ValueError(
                    f"{name}: region {bounds} under-covered by stored "
                    f"tiles ({covered}/{want} elements)")
            return out

        return read_region

    @staticmethod
    def _engine_read(eng, path, offset, length) -> np.ndarray:
        """Owning host array of [offset, offset+len) via chunked direct
        reads (restore needs the bytes to outlive the staging buffer, so
        one copy into the result buffer is inherent and counted)."""
        out = np.empty(length, dtype=np.uint8)
        fh = eng.open(path)
        pend: list = []
        try:
            # the planner owns the chunk split (the engine's chunk) and
            # the whole tile submits as ONE vectored batch — the engine
            # defers reads past its pool without blocking, and this
            # loop releases oldest-first, so the batch cannot deadlock
            (pend,) = plan_and_submit(eng, [(fh, offset, length)],
                                      klass="restore")
            pend = list(pend)
            pos = 0
            while pend:
                p = pend.pop(0)
                v = wait_exact(p)   # truncated tile must fail HERE
                out[pos:pos + v.nbytes] = v
                pos += v.nbytes
                p.release()
        finally:
            # a failed wait leaves younger reads in flight: they must be
            # released or their staging buffers are lost for the engine's
            # lifetime — and restore()'s fallback loop REUSES this engine
            # on the next candidate step
            for p in pend:
                p.release()
            eng.close(fh)
        if pos != length:
            # belt over wait_exact's braces: a truncated tile must fail
            # verification here, never reach the restored state as the
            # np.empty tail — the raise is what restore()'s
            # fallback-to-previous-step catches
            import errno as _errno
            raise OSError(_errno.EIO,
                          f"short tile read: {pos} of {length} bytes",
                          str(path))
        eng.stats.add(bounce_bytes=int(length))
        return out

    # -- plumbing ----------------------------------------------------------

    def _get_engine(self) -> tuple[StromEngine, bool]:
        if self._engine is not None:
            return self._engine, False
        from nvme_strom_tpu.io.faults import build_engine
        return build_engine(EngineConfig()), True

    @staticmethod
    def _sync() -> None:
        """Cross-process barrier (no-op single-process)."""
        import jax

        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("strom_ckpt")
