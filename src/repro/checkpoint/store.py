"""Sharded, atomic, async-capable, streaming + delta checkpointing.

Semantics a 1000-node deployment needs, implemented without external deps:

  * **Atomicity** — a checkpoint is written to ``step_<n>.tmp`` and renamed
    only after every shard file + the manifest are fsync'd (and the parent
    directory is fsync'd after the rename, so the publish itself is
    durable).  A crash mid-save never corrupts the latest-complete link;
    restore scans for the highest *complete* step, skipping ``.tmp``
    partials, stray non-step entries, and steps whose ``extra.json`` is
    torn (truncated/corrupt metadata must degrade to "resume one step
    earlier", never to a crash mid-restore).
  * **Streaming sharded saves** — with ``max_shard_bytes`` set, state
    leaves are flattened and cut into *pieces* of at most that many bytes,
    packed into ``shard_<k>.npz`` files each holding at most one budget's
    worth.  The writer stages (device_get's) one piece at a time and
    flushes a shard as soon as it fills, so peak staging memory is bounded
    by ONE shard, not by the O(n) state — the regime where a single
    ``savez`` of the whole pytree stops scaling.  Each shard is fsync'd
    through its own descriptor before the publish rename.
  * **Delta snapshots** — with ``delta=True``, each piece carries a
    content hash; pieces whose hash matches the previous complete step's
    are not rewritten — their manifest entry *references* the step that
    physically stores them (references always point at the physical home,
    so chains collapse to depth one and restore never walks more than one
    hop per piece).  Retention keeps referenced steps alive.
  * **Async save** — ``CheckpointManager.save(..., blocking=False)`` snap-
    shots device arrays to host (jax.device_get — the only synchronous
    part; in streaming mode jax leaves are held by immutable reference and
    staged piecewise in the background) and hands serialization to a
    background thread, overlapping disk I/O with the next steps (the SEM
    principle: overlap slow-tier I/O with compute).
  * **Elastic restore** — arrays are saved with their *global* shapes;
    ``restore_checkpoint`` re-shards onto whatever mesh the restored job
    runs with, so a job can restart on a smaller/larger pod count.
  * **Retention** — ``keep`` bounds disk usage; the newest ``keep`` steps
    survive, plus any older step a surviving delta manifest references.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from pathlib import Path
from typing import Any, Iterator, Optional

import jax
import ml_dtypes
import numpy as np

__all__ = [
    "CheckpointCorruptionError",
    "save_checkpoint",
    "restore_checkpoint",
    "latest_step",
    "load_extra",
    "CheckpointManager",
]

_MANIFEST = "manifest.json"
_EXTRA = "extra.json"


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint directory passed the completeness scan (manifest
    present, not ``.tmp``) but its contents do not match the manifest —
    e.g. a shard file holding fewer leaves than ``num_leaves``, a missing
    delta-referenced shard, or a torn ``extra.json``.  Raised instead of
    unflattening a short leaf list into garbage."""


def _step_num(name: str) -> Optional[int]:
    """``step_<n>`` -> n, or None for stray non-step entries (a user's
    ``step_old.bak``, editor droppings) — scanners must skip, not crash."""
    tail = name.split("_", 1)[1] if "_" in name else ""
    return int(tail) if tail.isdigit() else None


def _fsync_path(path: Path) -> None:
    """fsync a file (or directory) by path — directories need an O_RDONLY
    descriptor; plain files get one too, after their writer has closed."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)

# numpy can't serialize ml_dtypes (bfloat16 etc.) through savez — round-trip
# them through a same-width integer view, recording the true dtype in the
# manifest.
_VIEW_AS = {"bfloat16": np.uint16, "float8_e4m3fn": np.uint8, "float8_e5m2": np.uint8}


def _savable(a: np.ndarray) -> tuple[np.ndarray, str]:
    name = a.dtype.name
    if name in _VIEW_AS:
        return a.view(_VIEW_AS[name]), name
    return a, name


def _restore_dtype(a: np.ndarray, name: str) -> np.ndarray:
    if name in _VIEW_AS:
        return a.view(getattr(ml_dtypes, name))
    return a


def _flatten(tree: Any):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def _extra_ok(step_dir: Path) -> bool:
    """True when the step's ``extra.json`` is absent or parseable.  A torn
    extra (truncated by a crash mid-publish on a non-atomic filesystem, or
    a bit flip) makes the step un-resumable — the fingerprint guard cannot
    run — so completeness scans must treat it as incomplete."""
    epath = step_dir / _EXTRA
    if not epath.exists():
        return True
    try:
        json.loads(epath.read_text())
        return True
    except (json.JSONDecodeError, OSError):
        return False


def _fsync_json(path: Path, obj: Any) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())


# --------------------------------------------------------------------------
# streaming piece iteration
# --------------------------------------------------------------------------
def _piece_hash(piece: np.ndarray, dtype_name: str) -> str:
    h = hashlib.sha1()
    h.update(dtype_name.encode())
    h.update(np.int64(piece.size).tobytes())
    h.update(np.ascontiguousarray(piece).tobytes())
    return h.hexdigest()


def _leaf_pieces(leaf: Any, max_bytes: Optional[int]) -> Iterator[np.ndarray]:
    """Yield host-resident flat pieces of ``leaf``, each at most
    ``max_bytes`` (or the whole leaf when None).  jax leaves are sliced
    *before* transfer, so only one piece is ever staged on the host at a
    time — the writer's peak-staging bound."""
    is_jax = isinstance(leaf, jax.Array)
    flat = leaf.reshape(-1) if is_jax else np.ravel(np.asarray(leaf))
    n = int(flat.shape[0])
    itemsize = np.dtype(flat.dtype).itemsize if not is_jax \
        else np.dtype(flat.dtype).itemsize
    if max_bytes is None:
        epp = max(n, 1)
    else:
        epp = max(1, int(max_bytes) // max(itemsize, 1))
    if n == 0:
        yield np.asarray(jax.device_get(flat)) if is_jax else flat
        return
    for a in range(0, n, epp):
        piece = flat[a:a + epp]
        yield np.asarray(jax.device_get(piece)) if is_jax else \
            np.asarray(piece)


def _prev_manifest(directory: Path, step: int) -> Optional[dict]:
    """Newest complete step's manifest strictly below ``step`` (the delta
    base), or None."""
    best, best_d = None, None
    if not directory.exists():
        return None
    for d in directory.iterdir():
        if not d.name.startswith("step_") or d.name.endswith(".tmp"):
            continue
        s = _step_num(d.name)
        if s is None or s >= step or not (d / _MANIFEST).exists():
            continue
        if best is None or s > best:
            best, best_d = s, d
    if best_d is None:
        return None
    try:
        return json.loads((best_d / _MANIFEST).read_text())
    except (json.JSONDecodeError, OSError):
        return None


def save_checkpoint(
    directory: str | Path, step: int, tree: Any, *, process: int = 0,
    extra: Optional[dict] = None, max_shard_bytes: Optional[int] = None,
    delta: bool = False, telemetry: Optional[dict] = None,
) -> Path:
    """Write one atomic checkpoint; returns the final step directory.

    ``extra``: an optional JSON-serializable dict written as ``extra.json``
    inside the step directory (published under the same atomic rename) —
    the recovery layer stores its run fingerprint there so mismatches can
    be diagnosed *before* any array is unflattened.

    ``max_shard_bytes``: stream the state out in shards of at most this
    many bytes each (see module docstring) — peak staging memory is one
    shard, not the whole pytree.  ``delta=True`` additionally skips pieces
    whose content hash is unchanged since the previous complete step,
    recording a reference to that step's physical copy instead.  Both
    default off, which writes the legacy single-``npz`` layout.

    ``telemetry``: optional mutable dict; the streaming writer records
    ``stage_peak_bytes`` (max bytes staged on host at once — the measured
    O(1-shard) bound), ``bytes_written`` (fresh payload bytes, the delta
    savings measure), and ``shard_files``.
    """
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    final = directory / f"step_{step:08d}"
    tmp = directory / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    leaves, treedef = _flatten(tree)
    if max_shard_bytes is None and not delta:
        _write_legacy(tmp, leaves, treedef, process)
    else:
        _write_streaming(tmp, directory, step, leaves, treedef,
                         max_shard_bytes=max_shard_bytes, delta=delta,
                         telemetry=telemetry)
    if extra is not None:
        _fsync_json(tmp / _EXTRA, extra)
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    # fsync the parent directory so the rename itself survives a crash —
    # without this the atomicity docstring holds for file *contents* only.
    _fsync_path(directory)
    return final


def _write_legacy(tmp: Path, leaves: list, treedef, process: int) -> None:
    host = [np.asarray(jax.device_get(l)) for l in leaves]
    pairs = [_savable(a) for a in host]
    shard = tmp / f"proc{process}.npz"
    # write + fsync the shard through one descriptor: np.savez(path) would
    # close the file without a durability barrier, so a crash after the
    # rename below could still publish a manifest pointing at unsynced data.
    with open(shard, "wb") as f:
        np.savez(f, **{f"a{i}": a for i, (a, _) in enumerate(pairs)})
        f.flush()
        os.fsync(f.fileno())
    manifest = {
        "step": _step_num(tmp.name.removesuffix(".tmp")),
        "num_leaves": len(leaves),
        "treedef": str(treedef),
        "dtypes": [name for _, name in pairs],
        "shapes": [list(a.shape) for a in host],
        "processes": 1,
    }
    _fsync_json(tmp / _MANIFEST, manifest)


def _write_streaming(
    tmp: Path, directory: Path, step: int, leaves: list, treedef,
    *, max_shard_bytes: Optional[int], delta: bool,
    telemetry: Optional[dict],
) -> None:
    """The v2 writer: leaves cut into <=``max_shard_bytes`` pieces, packed
    greedily into fsync'd shard files, unchanged pieces (``delta``)
    referenced from their physical home step instead of rewritten."""
    prev = _prev_manifest(directory, step) if delta else None
    prev_leaves = (prev or {}).get("leaves")

    budget = int(max_shard_bytes) if max_shard_bytes is not None else None
    pending: dict = {}          # key -> host piece, the open shard
    pending_bytes = 0
    shard_files: list[str] = []
    peak_stage = 0
    bytes_written = 0
    entries = []
    dtype_names = []

    def _flush() -> None:
        nonlocal pending, pending_bytes
        if not pending:
            return
        name = f"shard_{len(shard_files):05d}.npz"
        with open(tmp / name, "wb") as f:
            np.savez(f, **pending)
            f.flush()
            os.fsync(f.fileno())
        shard_files.append(name)
        pending = {}
        pending_bytes = 0

    for i, leaf in enumerate(leaves):
        first = None
        pieces = []
        for j, piece in enumerate(_leaf_pieces(leaf, budget)):
            view, dtype_name = _savable(piece)
            if first is None:
                first = dtype_name
            h = _piece_hash(view, dtype_name)
            ref = None
            if prev_leaves is not None and i < len(prev_leaves):
                pl = prev_leaves[i]
                if (pl.get("dtype") == dtype_name
                        and j < len(pl.get("pieces", []))
                        and pl["pieces"][j].get("h") == h
                        and pl["pieces"][j].get("n") == int(view.size)):
                    ref = pl["pieces"][j]
            if ref is not None:
                # unchanged since the delta base: reference its physical
                # home (already depth-one: the base's entry points at the
                # step that actually stores the bytes).
                pieces.append({"h": h, "n": int(view.size),
                               "step": int(ref["step"]),
                               "shard": ref["shard"], "key": ref["key"]})
            else:
                key = f"a{i}_p{j}"
                if (budget is not None and pending
                        and pending_bytes + view.nbytes > budget):
                    _flush()
                pending[key] = view
                pending_bytes += int(view.nbytes)
                peak_stage = max(peak_stage, pending_bytes)
                bytes_written += int(view.nbytes)
                pieces.append({"h": h, "n": int(view.size), "step": step,
                               "shard": None, "key": key})
            del piece, view
        entries.append({"dtype": first, "pieces": pieces})
        dtype_names.append(first)
    _flush()
    # shard names are only known once flushed: resolve the fresh pieces'
    # shard field by replaying the same packing order.
    fresh_keys = [p["key"] for e in entries for p in e["pieces"]
                  if p["shard"] is None]
    key_to_shard = {}
    for name in shard_files:
        with np.load(tmp / name) as z:
            for k in z.files:
                key_to_shard[k] = name
    for e in entries:
        for p in e["pieces"]:
            if p["shard"] is None:
                p["shard"] = key_to_shard[p["key"]]
    assert all(p["shard"] is not None for e in entries for p in e["pieces"]), \
        fresh_keys

    for e, leaf in zip(entries, leaves):
        e["shape"] = list(np.shape(leaf))
    manifest = {
        "format": 2,
        "step": step,
        "num_leaves": len(leaves),
        "treedef": str(treedef),
        "dtypes": dtype_names,
        "shapes": [e["shape"] for e in entries],
        "leaves": entries,
        "shards": shard_files,
        "delta_base": (prev or {}).get("step"),
        "stored_bytes": bytes_written,
        "processes": 1,
    }
    _fsync_json(tmp / _MANIFEST, manifest)
    if telemetry is not None:
        telemetry["stage_peak_bytes"] = max(
            int(telemetry.get("stage_peak_bytes", 0)), peak_stage)
        telemetry["bytes_written"] = (
            int(telemetry.get("bytes_written", 0)) + bytes_written)
        telemetry["shard_files"] = (
            int(telemetry.get("shard_files", 0)) + len(shard_files))


def latest_step(directory: str | Path) -> Optional[int]:
    """Highest step with a complete manifest (ignores .tmp partials, stray
    non-numeric ``step_*`` entries, and steps whose ``extra.json`` is torn
    — a truncated metadata file must cost one step of progress, not the
    whole restore)."""
    directory = Path(directory)
    if not directory.exists():
        return None
    steps = []
    for d in directory.iterdir():
        if d.name.startswith("step_") and not d.name.endswith(".tmp"):
            s = _step_num(d.name)
            if s is not None and (d / _MANIFEST).exists() and _extra_ok(d):
                steps.append(s)
    return max(steps) if steps else None


def load_extra(directory: str | Path, step: int) -> Optional[dict]:
    """The ``extra`` dict saved with a step, or None if none was.  A
    present-but-unparseable ``extra.json`` raises
    :class:`CheckpointCorruptionError` naming the step — callers resuming
    a specific step must not mistake torn metadata for "no metadata"."""
    epath = Path(directory) / f"step_{step:08d}" / _EXTRA
    if not epath.exists():
        return None
    try:
        return json.loads(epath.read_text())
    except json.JSONDecodeError as e:
        raise CheckpointCorruptionError(
            f"checkpoint step {step} under {directory} has a torn "
            f"extra.json ({e.msg} at char {e.pos}); the step cannot be "
            f"fingerprint-checked.  latest_step() skips such steps — "
            f"resume from an earlier complete snapshot or delete the "
            f"corrupt step directory."
        ) from e


def _load_v2_leaves(directory: Path, manifest: dict) -> list:
    """Assemble leaves from a streaming/delta manifest, following each
    piece to the step that physically stores it.  One shard member is
    resident at a time per piece copy — restore staging mirrors the
    writer's bound."""
    handles: dict = {}

    def shard(step: int, name: str):
        key = (step, name)
        z = handles.get(key)
        if z is None:
            p = directory / f"step_{step:08d}" / name
            if not p.exists():
                raise CheckpointCorruptionError(
                    f"checkpoint under {directory} is corrupt: shard "
                    f"{name} of step {step} (referenced by a delta "
                    f"manifest) is missing — was the base step deleted "
                    f"outside the manager's retention?"
                )
            z = handles[key] = np.load(p)
        return z

    leaves = []
    try:
        for i, e in enumerate(manifest["leaves"]):
            n = int(np.prod(e["shape"], dtype=np.int64)) if e["shape"] \
                else 1
            stored_dtype = np.dtype(_VIEW_AS.get(e["dtype"], e["dtype"]))
            flat = np.empty(max(n, sum(p["n"] for p in e["pieces"])),
                            stored_dtype)
            off = 0
            for p in e["pieces"]:
                z = shard(int(p["step"]), p["shard"])
                if p["key"] not in z.files:
                    raise CheckpointCorruptionError(
                        f"checkpoint under {directory} is corrupt: shard "
                        f"{p['shard']} of step {p['step']} has no entry "
                        f"{p['key']} promised by the manifest"
                    )
                piece = z[p["key"]]
                if int(piece.size) != int(p["n"]):
                    raise CheckpointCorruptionError(
                        f"checkpoint under {directory} is corrupt: piece "
                        f"{p['key']} holds {int(piece.size)} elements, "
                        f"manifest promises {p['n']}"
                    )
                flat[off:off + piece.size] = piece.reshape(-1)
                off += int(piece.size)
            a = _restore_dtype(flat[:max(n, 0)].reshape(e["shape"]),
                               e["dtype"])
            leaves.append(a)
    finally:
        for z in handles.values():
            z.close()
    return leaves


def restore_checkpoint(
    directory: str | Path,
    target_tree: Any,
    step: Optional[int] = None,
    *,
    shardings: Any = None,
    as_numpy: bool = False,
) -> tuple[Any, int]:
    """Restore into the structure of ``target_tree``.

    ``shardings`` (same pytree structure, NamedSharding leaves) re-shards
    the restored global arrays — pass the *new* mesh's shardings to restart
    elastically on a different topology.

    ``as_numpy`` keeps the restored leaves as host numpy arrays (exact
    saved dtypes — ``jnp.asarray`` would silently downcast float64/int64
    when x64 is off), for host-side consumers like the work queue.

    Both layouts restore transparently: the legacy single-``npz`` step and
    the streaming/delta manifest (whose pieces may live in earlier steps'
    shards — the manager's retention keeps those alive).
    """
    directory = Path(directory)
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no complete checkpoint under {directory}")
    d = directory / f"step_{step:08d}"
    manifest = json.loads((d / _MANIFEST).read_text())
    if manifest.get("format") == 2:
        leaves = _load_v2_leaves(directory, manifest)
    else:
        data = np.load(d / "proc0.npz")
        if len(data.files) != manifest["num_leaves"]:
            raise CheckpointCorruptionError(
                f"checkpoint {d} is corrupt: shard holds {len(data.files)} "
                f"leaves but the manifest promises {manifest['num_leaves']}"
            )
        leaves = [
            _restore_dtype(data[f"a{i}"], manifest["dtypes"][i])
            for i in range(len(data.files))
        ]
    _, treedef = _flatten(target_tree)
    if treedef.num_leaves != len(leaves):
        raise CheckpointCorruptionError(
            f"checkpoint {d} holds {len(leaves)} leaves but the restore "
            f"target has {treedef.num_leaves}"
        )
    if shardings is not None:
        sh_leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "device_set")
        )
        leaves = [jax.device_put(a, s) for a, s in zip(leaves, sh_leaves)]
    elif not as_numpy:
        leaves = [jax.numpy.asarray(a) for a in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves), step


class CheckpointManager:
    """Retention + async save around the atomic writer.

    ``max_shard_bytes`` / ``delta`` select the streaming layout for every
    save through this manager (see :func:`save_checkpoint`); ``telemetry``
    receives the writer's staging/bytes odometers."""

    def __init__(self, directory: str | Path, keep: int = 3, *,
                 max_shard_bytes: Optional[int] = None, delta: bool = False,
                 telemetry: Optional[dict] = None):
        self.directory = Path(directory)
        self.keep = keep
        self.max_shard_bytes = max_shard_bytes
        self.delta = bool(delta)
        self.telemetry = telemetry
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    @property
    def _streaming(self) -> bool:
        return self.max_shard_bytes is not None or self.delta

    def wait(self):
        """Block until the in-flight async save (if any) completes."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, step: int, tree: Any, *, blocking: bool = True,
             extra: Optional[dict] = None):
        """Snapshot to host, then serialize (optionally in background).

        In streaming mode the host snapshot copies only *mutable* (numpy)
        leaves; jax arrays are immutable, so the background writer stages
        them piecewise — peak staging stays at one shard even for async
        saves."""
        self.wait()
        leaves, treedef = _flatten(tree)
        if self._streaming:
            host = [np.array(l, copy=True)
                    if isinstance(l, np.ndarray) else l for l in leaves]
        else:
            host = [np.asarray(jax.device_get(l)) for l in leaves]
        snapshot = jax.tree_util.tree_unflatten(treedef, host)

        def _write():
            try:
                save_checkpoint(self.directory, step, snapshot, extra=extra,
                                max_shard_bytes=self.max_shard_bytes,
                                delta=self.delta, telemetry=self.telemetry)
                self._gc()
            except BaseException as e:  # surfaced on next wait()/save()
                self._error = e

        if blocking:
            _write()
            if self._error is not None:
                err, self._error = self._error, None
                raise err
        else:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()

    def restore(self, target_tree: Any, *, shardings: Any = None):
        return restore_checkpoint(self.directory, target_tree, shardings=shardings)

    def _gc(self):
        steps = sorted(
            s
            for d in self.directory.iterdir()
            if d.name.startswith("step_") and not d.name.endswith(".tmp")
            and (s := _step_num(d.name)) is not None
            and (d / _MANIFEST).exists()
        )
        retained = set(steps[-self.keep:]) if self.keep else set()
        # Delta manifests reference earlier steps' shards: a retained
        # step's physical homes must survive retention too, or restore
        # would meet a missing-shard CheckpointCorruptionError.
        for s in sorted(retained, reverse=True):
            mpath = self.directory / f"step_{s:08d}" / _MANIFEST
            try:
                manifest = json.loads(mpath.read_text())
            except (json.JSONDecodeError, OSError):  # pragma: no cover
                continue
            for e in manifest.get("leaves") or []:
                for p in e["pieces"]:
                    retained.add(int(p["step"]))
        for s in steps:
            if s not in retained:
                shutil.rmtree(self.directory / f"step_{s:08d}",
                              ignore_errors=True)
