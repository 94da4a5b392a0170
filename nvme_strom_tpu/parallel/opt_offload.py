"""NVMe-offloaded optimizer state: Adam moments live on SSD, not HBM.

Adam triples a model's training memory: parameters plus two same-shaped
moment tensors.  On a TPU the parameters must be resident for fwd/bwd,
but the moments are touched exactly once per step — a streaming access
pattern, which is precisely what the engine's NVMe path is for
(SURVEY.md §3.5: the reference exists to feed accelerators data that
doesn't fit device memory; this module applies that identity to the
training loop's own state, the way ZeRO-Offload does for GPU+host-DRAM —
here the tier is NVMe through the O_DIRECT engine).

Per ``update(params, grads)``:

  1. group g's moment slots stream NVMe → staging → device
     (``DeviceStream``, chunk-pipelined, device-side assembly — no host
     concatenation buffer);
  2. a per-group jitted Adam update consumes (p, grad, m, v) and donates
     the moment buffers;
  3. updated moments stream back device → NVMe one group LATE: the
     device→host copy starts async (``copy_to_host_async``) and the
     ``submit_write``s are deferred until the next group has streamed
     in and dispatched — so neither the D2H nor the NVMe write ever
     blocks the group loop (pipelined ``submit_write``, O_DIRECT when
     alignment allows, bounced+counted otherwise).

HBM therefore holds the moments of TWO adjacent groups (default
2×64 MiB: the one updating plus the one riding home) instead of 2× the
model: a 16 GiB HBM chip can Adam-train parameters that would
otherwise need ~3× their size in HBM.  The cost is 2 reads + 2 writes
of the moment bytes per step, which the bench row (config 14) prices
against the in-HBM step.

Durability model: moments update IN PLACE (the no-double-write point of
offloading).  Each update commits a ``dirty`` marker before its first
slot write and clears it (with the advanced ``step``) only after every
write drains — so a crash mid-step, which leaves a MIX of steps in the
file, is detected and refused at resume rather than silently diverging.
Pair restores with the params checkpoint matching the manifest step
(checkpoint/manager.py; train_lm enforces this).  Transient write
failures (EIO/ENOSPC/short) are recovered below this layer when the
engine carries the resilient write mirror (``STROM_RESILIENT=1`` or an
explicit ``ResilientEngine`` — docs/RESILIENCE.md): slot writes are
exclusively-owned ranges, so a retry rewriting the same bytes is
idempotent and the dirty/step protocol above is unaffected.

Multi-host: each process owns a PER-PROCESS moment file holding the
moments of its locally-addressable parameter shards (unique shard
indices only — replicated leaves store one copy per process, fanned
back out on read).  The moment path needs no collectives: reads
assemble global arrays with ``make_array_from_single_device_arrays``,
writes serialize local shards, and each process commits its own
manifest — the next train step's existing collective is the barrier,
exactly the collective-free design checkpoint ``save_async`` uses.
Cross-process consistency is enforced at resume: an allgather of
(step, dirty) refuses a mix of steps or any dirty shard file on ANY
process.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from nvme_strom_tpu.checkpoint.manager import _norm_index
from nvme_strom_tpu.io.engine import StromEngine
from nvme_strom_tpu.ops.bridge import (
    DeviceStream, split_ranges, submit_chunked_writes)
from nvme_strom_tpu.utils.config import EngineConfig

_ALIGN = 4096
_MANIFEST_VERSION = 1


def _align_up(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _piece_key(index, shape) -> tuple:
    """A shard's index normalized to ((start, stop), ...) bounds — the
    identity that dedupes replicated shards and matches live shards to
    manifest slots.  Same normalization the checkpoint tile index uses
    (checkpoint/manager._norm_index), so moment shards and checkpoint
    tiles can never disagree on shard identity."""
    return _norm_index(index, shape)


def _local_pieces(arr):
    """Unique locally-addressable shards of ``arr``: a list of
    {key, shape} in first-seen order over device-id-sorted shards, plus
    the device→piece placement.  Replicated leaves collapse to one
    stored piece fanned out to every holding device."""
    shards = sorted(arr.addressable_shards, key=lambda sh: sh.device.id)
    pieces: list = []
    seen: dict = {}
    placement: list = []            # (device, piece_number)
    for sh in shards:
        key = _piece_key(sh.index, arr.shape)
        if key not in seen:
            seen[key] = len(pieces)
            pieces.append({"key": key,
                           "shape": tuple(int(x) for x in sh.data.shape)})
        placement.append((sh.device, seen[key]))
    return pieces, placement


class OffloadedAdam:
    """Adam(W) whose m/v moments live in an NVMe-backed file.

    ``path`` is a directory holding ``moments.bin`` + ``moments.json``
    (multi-process: ``moments-{proc:05d}.*`` per process — a shared dir
    or per-host local NVMe both work).
    The layout derives from ``params`` (flat or nested pytree); an
    existing manifest that matches the layout resumes (``.step`` picks
    up where it left off), anything else is created zero-initialised.

    ``update(params, grads)`` returns new params and advances the
    NVMe-resident moments; it is numerically identical to
    ``optax.adamw(lr, b1, b2, eps, weight_decay)`` (bias-corrected,
    decoupled weight decay) — pinned by tests/test_opt_offload.py.

    ``moment_dtype`` trades moment precision for half the NVMe traffic
    (bf16 moments ≈ the fp32 trajectory for pretraining-scale lr, but
    the parity guarantee above holds only for float32).
    """

    def __init__(self, path, params, *, lr,
                 b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0,
                 group_bytes: int = 64 << 20,
                 moment_dtype=jnp.float32,
                 engine: Optional[StromEngine] = None,
                 config: Optional[EngineConfig] = None,
                 depth: int = 4):
        self._multi = jax.process_count() > 1
        # lr: float, or a schedule callable step->lr (optax schedules
        # qualify) evaluated host-side at each update's .step — the
        # update loop is host-driven anyway, so no retrace
        self.lr = lr if callable(lr) else float(lr)
        self.b1, self.b2 = float(b1), float(b2)
        self.eps, self.weight_decay = float(eps), float(weight_decay)
        self.moment_dtype = jnp.dtype(moment_dtype)
        self._own_engine = engine is None
        if engine is None:
            from nvme_strom_tpu.io.faults import build_engine
            engine = build_engine(config or EngineConfig())
        self.engine = engine
        self.stream = DeviceStream(self.engine, depth=depth, drain="ready",
                                   klass="restore")

        try:
            self._init_state(path, params, group_bytes)
        except BaseException:
            # refusal paths (dirty/layout/step-mismatch) and I/O errors
            # must not leak the engine we just created: its IO threads
            # and fds outlive the exception otherwise
            if self._own_engine:
                self.engine.close_all()
            raise

    def _init_state(self, path, params, group_bytes: int) -> None:
        leaves, self._treedef = jax.tree_util.tree_flatten_with_path(params)
        self._names = [jax.tree_util.keystr(kp) for kp, _ in leaves]
        if len(set(self._names)) != len(self._names):
            raise ValueError("duplicate leaf names in params tree")
        order = sorted(range(len(leaves)), key=lambda i: self._names[i])
        self._order = order

        # ---- layout: aligned m/v slots; single-process keeps the
        # round-3 full-leaf format (and its on-disk manifests), multi-
        # process stores one slot pair PER UNIQUE LOCAL SHARD ----
        self._layout: Dict[str, dict] = {}
        off = 0
        isz = self.moment_dtype.itemsize
        for i in order:
            name = self._names[i]
            arr = leaves[i][1]
            if not self._multi:
                nbytes = int(np.prod(arr.shape, dtype=np.int64)) * isz \
                    if arr.shape else isz
                self._layout[name] = {
                    "shape": tuple(int(s) for s in arr.shape),
                    "nbytes": int(nbytes),
                    "off_m": off,
                    "off_v": off + _align_up(nbytes),
                }
                off += 2 * _align_up(nbytes)
                continue
            if not hasattr(arr, "addressable_shards"):
                raise TypeError(
                    f"multi-process OffloadedAdam needs jax.Array "
                    f"params (leaf {name} is {type(arr).__name__}) — "
                    "the moment shards follow the param sharding")
            pieces, placement = _local_pieces(arr)
            fanout = [0] * len(pieces)      # local devices per piece
            for _dev, pno in placement:
                fanout[pno] += 1
            plist = []
            for pno, pc in enumerate(pieces):
                nbytes = (int(np.prod(pc["shape"], dtype=np.int64)) * isz
                          if pc["shape"] else isz)
                plist.append({"key": pc["key"], "shape": pc["shape"],
                              "nbytes": int(nbytes),
                              "fanout": fanout[pno],
                              "off_m": off,
                              "off_v": off + _align_up(nbytes)})
                off += 2 * _align_up(nbytes)
            self._layout[name] = {
                "shape": tuple(int(s) for s in arr.shape),
                "pieces": plist,
            }
        self._total_bytes = off

        # ---- groups: consecutive slots, ~group_bytes of HBM each ----
        self._groups: list[list[str]] = []
        cur: list[str] = []
        cur_b = 0
        for i in order:
            name = self._names[i]
            # partition on GLOBAL bytes: local shard sizes can differ
            # across processes (uneven splits), and the groups define
            # the jitted SPMD programs every process must run in
            # lockstep — the metric must be process-invariant
            b = 2 * self._global_leaf_bytes(name)
            if cur and cur_b + b > group_bytes:
                self._groups.append(cur)
                cur, cur_b = [], 0
            cur.append(name)
            cur_b += b
        if cur:
            self._groups.append(cur)

        os.makedirs(path, exist_ok=True)
        # per-process files: each host/process owns the moments of ITS
        # param shards; a shared dir works (distinct names) and so does
        # per-host local NVMe (same name, different disk)
        suffix = f"-{jax.process_index():05d}" if self._multi else ""
        self.data_path = os.path.join(path, f"moments{suffix}.bin")
        self.manifest_path = os.path.join(path, f"moments{suffix}.json")
        self.step = 0
        local_err = None
        try:
            # resume AND zero-create are both local-failure-prone (I/O,
            # corrupt manifest); in multi-process mode ANY local failure
            # must reach the allgather below rather than killing this
            # process while the others block in it
            if not self._try_resume():
                self._create_zeroed()
        except Exception as e:  # noqa: BLE001 — deferred to allgather
            if not self._multi:
                raise
            local_err = f"{type(e).__name__}: {e}"
        if self._multi:
            from jax.experimental import multihost_utils
            payload = np.array([self.step, 1 if local_err else 0],
                               np.int64)
            all_ = multihost_utils.process_allgather(payload)
            if all_[:, 1].any():
                raise ValueError(
                    local_err or "another process refused to resume "
                    "its moment shard file (dirty or layout mismatch) — "
                    "all processes must restore from matching state")
            if (all_[:, 0] != all_[0, 0]).any():
                raise ValueError(
                    f"moment shard files disagree on the optimizer "
                    f"step across processes ({sorted(set(all_[:, 0].tolist()))}) "
                    "— a previous run crashed between per-process "
                    "commits; restore params from the matching "
                    "checkpoint into fresh moment dirs")
        self._fh = self.engine.open(self.data_path, writable=True)
        self._update_fns: Dict[int, object] = {}

    def _leaf_bytes(self, name: str) -> int:
        """LOCAL stored bytes of one moment tensor (sum of this
        process's unique shards)."""
        d = self._layout[name]
        if "pieces" in d:
            return sum(p["nbytes"] for p in d["pieces"])
        return d["nbytes"]

    def _leaf_hbm_bytes(self, name: str) -> int:
        """LOCAL HBM one moment tensor occupies during its group's
        update: replicated pieces are fanned out to every holding
        device, so they count once per device, not once per slot."""
        d = self._layout[name]
        if "pieces" in d:
            return sum(p["nbytes"] * p.get("fanout", 1)
                       for p in d["pieces"])
        return d["nbytes"]

    def _global_leaf_bytes(self, name: str) -> int:
        """GLOBAL bytes of one moment tensor — process-invariant, the
        group-partitioning metric."""
        d = self._layout[name]
        n = int(np.prod(d["shape"], dtype=np.int64)) if d["shape"] else 1
        return n * self.moment_dtype.itemsize

    # ------------------------------------------------------------------
    def _manifest(self, dirty: bool = False) -> dict:
        return {
            "version": _MANIFEST_VERSION,
            "step": self.step,
            "dirty": dirty,
            "dtype": self.moment_dtype.name,
            "align": _ALIGN,
            "total_bytes": self._total_bytes,
            "leaves": json.loads(json.dumps(self._layout)),
        }

    def _try_resume(self) -> bool:
        try:
            with open(self.manifest_path) as f:
                m = json.load(f)
        except (OSError, json.JSONDecodeError):
            return False
        ours = self._manifest()
        theirs_layout = m.get("leaves", {})
        ours_layout = ours["leaves"]    # _manifest already normalized
        if (m.get("version") != _MANIFEST_VERSION
                or m.get("dtype") != ours["dtype"]
                or theirs_layout != ours_layout):
            raise ValueError(
                f"existing moment file at {self.manifest_path} has a "
                "different layout/dtype than these params — refusing to "
                "overwrite optimizer state; point at a fresh directory "
                "or delete it explicitly")
        if m.get("dirty"):
            raise ValueError(
                f"moment file at {self.manifest_path} is marked dirty: a "
                f"previous update crashed mid-step (after step "
                f"{int(m['step'])}), so slots hold a MIX of steps — "
                "resuming would silently diverge.  Restore params from "
                "the matching checkpoint into a fresh moment dir, or "
                "delete this one explicitly")
        self.step = int(m["step"])
        return True

    def _create_zeroed(self) -> None:
        fh = self.engine.open(self.data_path, writable=True)
        try:
            chunk = self.engine.config.chunk_bytes
            zeros = np.zeros(min(chunk, self._total_bytes), np.uint8)
            pend: list = []
            for off in range(0, self._total_bytes, chunk):
                n = min(chunk, self._total_bytes - off)
                submit_chunked_writes(self.engine, fh, off, zeros[:n],
                                      pend)
            while pend:
                pend.pop(0).wait()
        finally:
            self.engine.close(fh)
        self.step = 0
        self._commit_manifest()

    def _commit_manifest(self, dirty: bool = False) -> None:
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._manifest(dirty), f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.manifest_path)

    # ------------------------------------------------------------------
    def _slots(self, name):
        """(off_m, off_v, nbytes, shape) per stored slot pair of a leaf —
        one pair for the whole leaf single-process, one per unique local
        shard multi-process."""
        d = self._layout[name]
        if "pieces" in d:
            return [(pc["off_m"], pc["off_v"], pc["nbytes"], pc["shape"])
                    for pc in d["pieces"]]
        return [(d["off_m"], d["off_v"], d["nbytes"], d["shape"])]

    def _group_ranges(self, names) -> tuple[list, list]:
        """Chunk-split (offset, length) ranges covering each slot of the
        group, plus per-slot chunk counts for device-side reassembly.
        The split rule comes from the shared planner
        (``io.plan.split_spans``) and the size is the engine's
        ``chunk_bytes``; the ranges then ride ``DeviceStream``'s
        vectored submission."""
        chunk = self.engine.config.chunk_bytes
        ranges: list[tuple[int, int]] = []
        counts: list[int] = []      # chunks per slot, m then v, slot order
        for n in names:
            for off_m, off_v, nbytes, _ in self._slots(n):
                for off in (off_m, off_v):
                    flat, cnt = split_ranges([(off, nbytes)], chunk)
                    ranges.extend(flat)
                    counts.append(cnt[0])
        return ranges, counts

    def _read_group(self, names, ps):
        """Moment slots NVMe → device arrays, chunk-pipelined; chunks
        assemble on device (jnp.concatenate), never in a host buffer.
        Multi-process: each stored piece is fanned out to every local
        device holding that shard index and the global moment array is
        built with ``make_array_from_single_device_arrays`` — no
        collectives on the moment path."""
        ranges, counts = self._group_ranges(names)
        chunks = list(self.stream.stream_ranges(self._fh, ranges))
        ms, vs = [], []
        it = iter(chunks)
        ci = iter(counts)
        for j, n in enumerate(names):
            d = self._layout[n]
            slot_arrays = []        # per slot: (m_piece, v_piece)
            for _, _, _, shape in self._slots(n):
                pair = []
                for _mv in range(2):
                    parts = [next(it) for _ in range(next(ci))]
                    flat = parts[0] if len(parts) == 1 \
                        else jnp.concatenate(parts)
                    pair.append(flat.view(self.moment_dtype)
                                .reshape(shape))
                slot_arrays.append(pair)
            if "pieces" not in d:
                m, v = slot_arrays[0]
                sh = getattr(ps[j], "sharding", None)
                if sh is not None:
                    m = jax.device_put(m, sh)
                    v = jax.device_put(v, sh)
                ms.append(m)
                vs.append(v)
                continue
            pieces, placement = _local_pieces(ps[j])
            want = [tuple(pc["key"]) for pc in d["pieces"]]
            have = [pc["key"] for pc in pieces]
            if have != want:
                raise ValueError(
                    f"leaf {n}: live sharding's local shards {have} do "
                    f"not match the moment file layout {want} — the "
                    "params' sharding changed since this optimizer was "
                    "built")
            m_dev = [jax.device_put(slot_arrays[pno][0], dev)
                     for dev, pno in placement]
            v_dev = [jax.device_put(slot_arrays[pno][1], dev)
                     for dev, pno in placement]
            gshape = d["shape"]
            ms.append(jax.make_array_from_single_device_arrays(
                gshape, ps[j].sharding, m_dev))
            vs.append(jax.make_array_from_single_device_arrays(
                gshape, ps[j].sharding, v_dev))
        return ms, vs

    def _stage_writeback(self, names, ms, vs, ps) -> list:
        """Normalize shardings and START the device→host copies of a
        group's updated moments, without blocking.

        The round-4 on-silicon attribution (config 14 v2 tag) put the
        step's residual in dispatch/sync: ``_write_group``'s
        ``np.asarray`` forces a full device round-trip per group INSIDE
        the group loop, so every group serialized compute → D2H → NVMe
        before the next group's reads began.  Staging here instead
        (async D2H via ``copy_to_host_async``) lets ``update`` defer
        the actual NVMe writes by one group — group g's moments ride
        the link home while group g+1 streams in and updates.  Costs
        one extra group of moments live in HBM (see
        ``peak_group_bytes``)."""
        staged = []
        for n, m, v, pref in zip(names, ms, vs, ps):
            d = self._layout[n]
            if "pieces" in d:
                # the update's outs are unpinned; land them on the
                # params' sharding so the local shard structure matches
                # the slots BEFORE the host copy starts
                sh = pref.sharding
                if m.sharding != sh:
                    m = jax.device_put(m, sh)
                if v.sharding != sh:
                    v = jax.device_put(v, sh)
            for arr in (m, v):
                try:
                    arr.copy_to_host_async()
                except (AttributeError, RuntimeError):
                    pass      # backend without async D2H: wait at write
            staged.append((n, m, v))
        return staged

    def _write_group(self, staged, pend) -> None:
        """NVMe-submit one previously staged group's moments (the
        ``np.asarray`` here completes the async D2H started in
        ``_stage_writeback`` — by now it has had a full group's
        read+update time to finish)."""
        for n, m, v in staged:
            d = self._layout[n]
            if "pieces" not in d:
                for off, arr in ((d["off_m"], m), (d["off_v"], v)):
                    host = np.asarray(arr).view(np.uint8).reshape(-1)
                    submit_chunked_writes(self.engine, self._fh, off,
                                          host, pend)
                continue
            for arr, which in ((m, "off_m"), (v, "off_v")):
                by_key = {}
                for shd in arr.addressable_shards:
                    by_key.setdefault(_piece_key(shd.index, arr.shape),
                                      shd)
                for pc in d["pieces"]:
                    shd = by_key.get(tuple(pc["key"]))
                    if shd is None:
                        raise ValueError(
                            f"leaf {n}: updated moment lost local shard "
                            f"{pc['key']} — sharding drifted mid-step")
                    host = np.asarray(shd.data).view(np.uint8).reshape(-1)
                    submit_chunked_writes(self.engine, self._fh,
                                          pc[which], host, pend)

    def _update_fn(self, gi: int):
        """Per-group jitted Adam update; moment buffers are donated."""
        if gi in self._update_fns:
            return self._update_fns[gi]
        b1, b2, eps, wd = self.b1, self.b2, self.eps, self.weight_decay
        mdt = self.moment_dtype

        def upd(ps, gs, ms, vs, t, lr):
            out_p, out_m, out_v = [], [], []
            for p, g, m, v in zip(ps, gs, ms, vs):
                g32 = g.astype(jnp.float32)
                m32 = m.astype(jnp.float32) * b1 + g32 * (1 - b1)
                v32 = v.astype(jnp.float32) * b2 + g32 * g32 * (1 - b2)
                mh = m32 / (1 - b1 ** t)
                vh = v32 / (1 - b2 ** t)
                step = mh / (jnp.sqrt(vh) + eps) + wd * p.astype(jnp.float32)
                out_p.append((p.astype(jnp.float32) - lr * step)
                             .astype(p.dtype))
                out_m.append(m32.astype(mdt))
                out_v.append(v32.astype(mdt))
            return out_p, out_m, out_v

        fn = jax.jit(upd, donate_argnums=(2, 3))
        self._update_fns[gi] = fn
        return fn

    def update(self, params, grads):
        """One Adam(W) step: returns the updated params tree; the
        NVMe-resident moments advance in place and ``.step`` increments
        (manifest committed after all writes drain)."""
        p_named = {jax.tree_util.keystr(kp): a for kp, a
                   in jax.tree_util.tree_flatten_with_path(params)[0]}
        g_leaves, g_def = jax.tree_util.tree_flatten_with_path(grads)
        g_named = {jax.tree_util.keystr(kp): a for kp, a in g_leaves}
        if set(p_named) != set(self._layout) or set(g_named) != set(
                self._layout):
            raise ValueError("params/grads tree does not match the "
                             "layout this optimizer was built for")
        t = jnp.float32(self.step + 1)
        lr = jnp.float32(self.lr(self.step) if callable(self.lr)
                         else self.lr)
        new_named: Dict[str, object] = {}
        pend: list = []
        # mark dirty BEFORE the first in-place slot write: a crash
        # mid-step leaves a mix of steps in the file, and only this
        # marker lets a resume detect it (the step counter alone cannot)
        self._commit_manifest(dirty=True)
        staged = None     # previous group's write-back, D2H in flight
        try:
            for gi, names in enumerate(self._groups):
                ps = [p_named[n] for n in names]
                gs = [g_named[n] for n in names]
                sh = [getattr(p, "sharding", None) for p in ps]
                ms, vs = self._read_group(names, ps)
                out_p, out_m, out_v = self._update_fn(gi)(
                    ps, gs, ms, vs, t, lr)
                # out_shardings are unpinned (m/v leave for NVMe anyway),
                # so GSPMD may have re-sharded p' — put each leaf back on
                # its own sharding (no-op when unchanged)
                out_p = [x if s is None or x.sharding == s
                         else jax.device_put(x, s)
                         for x, s in zip(out_p, sh)]
                # one-group-deep write pipeline: submit the PREVIOUS
                # group's NVMe writes (its async D2H has had this
                # group's read+update time to land), then stage this
                # group's D2H — no per-group device sync in the loop
                if staged is not None:
                    self._write_group(staged, pend)
                staged = self._stage_writeback(names, out_m, out_v, ps)
                for n, p in zip(names, out_p):
                    new_named[n] = p
            if staged is not None:
                self._write_group(staged, pend)
                staged = None
            # success drain MUST raise: a failed moment write that got
            # swallowed here would let the manifest claim a step whose
            # slots never landed
            while pend:
                pend.pop(0).wait()
        finally:
            # only reachable with work left when an exception is already
            # propagating — release without masking it
            while pend:
                try:
                    pend.pop(0).wait()
                except OSError:
                    pass
        self.step += 1
        self._commit_manifest()
        flat = [new_named[n] for n in self._names]
        return jax.tree_util.tree_unflatten(self._treedef, flat)

    # ------------------------------------------------------------------
    def moment_bytes(self) -> int:
        """NVMe footprint of the offloaded state (manifest total)."""
        return self._total_bytes

    def num_groups(self) -> int:
        """How many read→update→write rounds one step takes."""
        return len(self._groups)

    def peak_group_bytes(self) -> int:
        """Worst-case HBM the moments occupy during a step: the
        updating group plus the previous group whose write-back D2H is
        still in flight (the one-group-deep write pipeline)."""
        per_group = [sum(2 * self._leaf_hbm_bytes(n) for n in g)
                     for g in self._groups]
        if len(per_group) == 1:
            return per_group[0]
        return max(a + b for a, b in zip(per_group, per_group[1:]))

    def close(self) -> None:
        if getattr(self, "_fh", None) is not None:
            self.engine.close(self._fh)
            self._fh = None
        if self._own_engine and self.engine is not None:
            self.engine.close_all()
            self.engine = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
