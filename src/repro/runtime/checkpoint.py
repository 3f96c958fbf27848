"""Fault-tolerant checkpointing: async, sharded, content-addressed.

Checkpoints store the *canonical* (ungrouped, unstaged) parameter pytree, so
a restore may regroup for a completely different ExecutionPlan — this is the
mechanism behind elastic scaling (runtime/elastic.py): after a world-size
change the SearchEngine emits a new plan and the same checkpoint reshards
onto the new mesh via ``device_put`` with the new shardings.

Since live resize landed (runtime/resize.py) the checkpoint round trip is no
longer the *primary* elastic path: in-memory migration reshards live state
directly.  This module remains the fallback for real membership loss (the
old buffers are gone) and the equivalence oracle — both paths must produce
bitwise identical state, which the elastic tests and
``benchmarks/elastic_resize.py`` assert.

Format v2 (sharded, content-addressed — the default writer)::

    dir/
      blobs/<sha256-prefix>.gvck    one GVCK blob per unique leaf content
      stepNNNNNNNNN.json            index: leaf key -> {blob, dtype, shape}
      MANIFEST                      {"latest_step": N}

Every shard blob is named by the SHA-256 of its *uncompressed* bytes, so a
leaf whose content did not change between steps (frozen embeddings, opt
``count`` scalars, repeated saves under elastic churn) is written exactly
once and shared across step indexes — repeated saves cost only the index.
The per-shard layout is also the on-disk shape multi-host writes need: each
host can write just its own shard set and the per-step index merges them.
``_gc`` is index-aware refcounting GC: a blob survives until the last step
index referencing it is dropped.

Shard blobs and v1 single-file checkpoints share the 7-byte header::

    b"GVCK" | version u8 | codec u8 | serializer u8

The codec byte names the compression codec (zstd/zlib/raw — see the registry
in :mod:`repro.runtime.compression`; the writer auto-selects the best codec
available and readers refuse clearly when theirs is missing).  The
serializer byte names the payload encoding: 0 = the self-contained native
framing (JSON index + concatenated raw buffers, zero optional deps),
1 = msgpack (read-compatibility; only written when explicitly requested),
2 = a single raw leaf (v2 shard blobs; dtype/shape live in the step index).
Optional dependencies (``zstandard``, ``msgpack``) are imported lazily and
guarded — importing this module never requires them.

v1 single-file checkpoints (``stepNNN.ckpt``, the whole payload in one blob)
and legacy pre-header files (bare zstd-compressed msgpack) stay readable;
anything whose first bytes are neither a GVCK header nor a zstd frame is
rejected as corrupt with a clear error (:class:`CorruptCheckpointError`),
never routed into the legacy decoder's misleading missing-dependency path.

Async writes: :class:`CheckpointWriter` snapshots leaves with non-blocking
``copy_to_host_async`` device→host futures, then hashes/compresses/writes
on a background writer thread behind a bounded queue (double-buffering: the
step loop only ever blocks on the *previous* save still being in flight);
``wait()``/``close()`` drain on exit and surface writer-thread errors.  The
synchronous :func:`save` shares the same write path byte for byte, so it
remains the equivalence oracle (``benchmarks/checkpoint_async.py`` asserts
bitwise-identical output and a strictly lower step-loop blocking time).

Writes go to a temp name + atomic rename; a MANIFEST names the latest
complete step, so a host crash mid-write can never corrupt restore.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import queue
import struct
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.core.strategy import ExecutionPlan
from repro.runtime import compression

MAGIC = b"GVCK"
FORMAT_V1 = 1                  # single-file payload (read + opt-in write)
FORMAT_V2 = 2                  # sharded content-addressed layout (default)
FORMAT_VERSION = FORMAT_V1     # header byte of v1 blobs (back-compat alias)

SERIALIZER_NATIVE = 0
SERIALIZER_MSGPACK = 1
SERIALIZER_RAW_LEAF = 2        # v2 shard blobs: payload is one leaf's bytes

#: bytes of the SHA-256 hex digest used for blob names (128 bits)
_HASH_CHARS = 32


class CorruptCheckpointError(ValueError):
    """A checkpoint blob that is demonstrably truncated or corrupt — as
    opposed to one that merely needs an optional dependency to decode."""


# --------------------------------------------------------------------------
# payload serializers
# --------------------------------------------------------------------------

def _have_msgpack() -> bool:
    try:
        import msgpack  # noqa: F401
        return True
    except ImportError:
        return False


def _pack_native(payload: dict) -> bytes:
    """JSON index + concatenated raw buffers — no third-party deps."""
    index: dict = {}
    blobs: list[bytes] = []
    off = 0
    for key, rec in payload.items():
        data = rec["data"]
        index[key] = {"dtype": rec["dtype"], "shape": rec["shape"],
                      "offset": off, "length": len(data)}
        blobs.append(data)
        off += len(data)
    head = json.dumps(index).encode("utf-8")
    return struct.pack("<Q", len(head)) + head + b"".join(blobs)


def _unpack_native(buf: bytes) -> dict:
    if len(buf) < 8:
        raise CorruptCheckpointError(
            f"corrupt or truncated checkpoint payload: {len(buf)} bytes is "
            "too short for the native index header")
    (head_len,) = struct.unpack_from("<Q", buf, 0)
    if 8 + head_len > len(buf):
        raise CorruptCheckpointError(
            "corrupt or truncated checkpoint payload: index head of "
            f"{head_len} bytes exceeds the {len(buf)}-byte payload")
    index = json.loads(buf[8:8 + head_len].decode("utf-8"))
    base = 8 + head_len
    out = {}
    for key, rec in index.items():
        stop = base + rec["offset"] + rec["length"]
        if stop > len(buf):
            raise CorruptCheckpointError(
                f"corrupt or truncated checkpoint payload: leaf {key!r} "
                f"extends to byte {stop} of a {len(buf)}-byte payload")
        out[key] = {"dtype": rec["dtype"], "shape": rec["shape"],
                    "data": buf[base + rec["offset"]: stop]}
    return out


def _serialize(payload: dict, serializer: int) -> bytes:
    if serializer == SERIALIZER_MSGPACK:
        import msgpack

        return msgpack.packb(payload, use_bin_type=True)
    return _pack_native(payload)


def _deserialize(buf: bytes, serializer: int) -> dict:
    if serializer == SERIALIZER_MSGPACK:
        if not _have_msgpack():
            raise RuntimeError("checkpoint was serialized with msgpack, which "
                               "is not installed here")
        import msgpack

        return msgpack.unpackb(buf, raw=False)
    if serializer != SERIALIZER_NATIVE:
        raise ValueError(f"unknown checkpoint serializer byte {serializer}")
    return _unpack_native(buf)


# --------------------------------------------------------------------------
# blob encode/decode (header + codec + serializer)
# --------------------------------------------------------------------------

def encode_blob(payload: dict, *, codec: Optional[str] = None,
                use_msgpack: bool = False) -> bytes:
    """v1 whole-payload blob: header + compressed serialized payload dict."""
    c = compression.best_codec(codec)
    if use_msgpack and not _have_msgpack():
        # same contract as an explicit-but-unavailable codec: raise, don't
        # silently write a framing the caller's target reader can't parse
        raise RuntimeError("use_msgpack=True requested but msgpack is not "
                           "installed in this environment")
    serializer = SERIALIZER_MSGPACK if use_msgpack else SERIALIZER_NATIVE
    body = c.compress(_serialize(payload, serializer))
    return MAGIC + bytes([FORMAT_V1, c.fmt_byte, serializer]) + body


def _split_header(blob: bytes, what: str) -> tuple[int, int, int, bytes]:
    """(version, codec_byte, serializer, body) of a GVCK blob, or a clear
    corruption error.  Callers guarantee ``blob[:4] == MAGIC``."""
    if len(blob) < 7:
        raise CorruptCheckpointError(
            f"corrupt or truncated {what}: GVCK header cut short at "
            f"{len(blob)} bytes (a complete header is 7)")
    return blob[4], blob[5], blob[6], blob[7:]


def decode_blob(blob: bytes) -> dict:
    """Decode a v1 whole-payload blob (or a legacy pre-header file)."""
    if blob[:4] == MAGIC:
        version, codec_byte, serializer, body = _split_header(
            blob, "checkpoint file")
        if version == FORMAT_V2:
            raise ValueError(
                "this is a v2 shard blob (one leaf of a sharded checkpoint); "
                "restore it through its step index (stepNNNNNNNNN.json), not "
                "as a whole-checkpoint file")
        if version != FORMAT_V1:
            raise ValueError(f"unsupported checkpoint format version {version}")
        if serializer not in (SERIALIZER_NATIVE, SERIALIZER_MSGPACK):
            raise ValueError(f"unknown checkpoint serializer byte {serializer}")
        c = compression.codec_for_byte(codec_byte)
        if serializer == SERIALIZER_MSGPACK and not _have_msgpack():
            raise RuntimeError("checkpoint was serialized with msgpack, which "
                               "is not installed here")
        try:
            return _deserialize(c.decompress(body), serializer)
        except CorruptCheckpointError:
            raise
        except Exception as e:
            raise CorruptCheckpointError(
                f"corrupt or truncated checkpoint file: body failed to "
                f"decode ({type(e).__name__}: {e})") from e
    if blob[:4] == compression.LEGACY_ZSTD_MAGIC:
        return _decode_legacy(blob)
    raise CorruptCheckpointError(
        f"corrupt or truncated checkpoint file: first bytes {blob[:8]!r} "
        "are neither a GVCK header nor a legacy zstd frame")


def _decode_legacy(blob: bytes) -> dict:
    """Pre-header files: bare zstd-compressed msgpack."""
    try:
        import msgpack
        import zstandard
    except ImportError as e:
        raise RuntimeError(
            "legacy checkpoint (no GVCK header) needs the optional "
            "'zstandard' and 'msgpack' packages to restore; re-save it from "
            "an environment that has them") from e
    return msgpack.unpackb(zstandard.ZstdDecompressor().decompress(blob),
                           raw=False)


def encode_shard(raw: bytes, *, codec: Optional[str] = None) -> bytes:
    """v2 shard blob: header + compressed raw leaf bytes (metadata lives in
    the step index, keyed by the blob's content hash)."""
    c = compression.best_codec(codec)
    return (MAGIC + bytes([FORMAT_V2, c.fmt_byte, SERIALIZER_RAW_LEAF])
            + c.compress(raw))


def decode_shard(blob: bytes) -> bytes:
    if blob[:4] != MAGIC:
        raise CorruptCheckpointError(
            f"corrupt or truncated shard blob: first bytes {blob[:8]!r} are "
            "not a GVCK header")
    version, codec_byte, serializer, body = _split_header(blob, "shard blob")
    if version != FORMAT_V2 or serializer != SERIALIZER_RAW_LEAF:
        raise ValueError(
            f"not a v2 shard blob (version {version}, serializer "
            f"{serializer}); whole-checkpoint files decode via decode_blob")
    c = compression.codec_for_byte(codec_byte)
    try:
        return c.decompress(body)
    except Exception as e:
        raise CorruptCheckpointError(
            f"corrupt or truncated shard blob: decompress failed "
            f"({type(e).__name__}: {e})") from e


def content_hash(raw) -> str:
    """Content address of a shard: SHA-256 prefix of the raw leaf bytes
    (accepts any buffer — bytes, memoryview, or a contiguous ndarray)."""
    return hashlib.sha256(raw).hexdigest()[:_HASH_CHARS]


# --------------------------------------------------------------------------
# pytree <-> payload
# --------------------------------------------------------------------------

def _escape_part(part: str) -> str:
    """Make the '/' join unambiguous: a literal separator inside a leaf key
    would otherwise silently collide with a nested path."""
    return part.replace("\\", "\\\\").replace("/", "\\/")


def _path_key(path) -> str:
    return "/".join(_escape_part(str(getattr(p, "key", getattr(p, "idx", p))))
                    for p in path)


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[_path_key(path)] = leaf
    return flat


def begin_host_snapshot(*trees) -> None:
    """Kick off non-blocking device→host copies for every leaf.  The async
    writer's snapshot primitive: by the time the writer thread touches the
    values, the transfers have been overlapping with the step loop."""
    for tree in trees:
        if tree is None:
            continue
        for leaf in jax.tree_util.tree_leaves(tree):
            copy = getattr(leaf, "copy_to_host_async", None)
            if copy is not None:
                copy()


def _pin_host_leaves(tree):
    """Value-snapshot of the host-backed leaves: plain numpy arrays are
    mutable in place, so an in-flight async save must hold its own copy.
    Immutable device arrays pass through by reference (their values are
    already pinned; ``begin_host_snapshot`` owns their transfer)."""
    if tree is None:
        return None
    return jax.tree_util.tree_map(
        lambda x: x.copy() if isinstance(x, np.ndarray) else x, tree)


def canonical_checkpoint_state(trainer, params, opt_state=None, *,
                               snapshot: bool = True):
    """Fold a trainer's layout (scan groups / pipeline stages) back into the
    canonical (ungrouped, unstaged) pytrees checkpoints store — the single
    canonicalization both trainers' ``checkpoint_state`` hooks and
    ``resize.canonical_state`` share.  With ``snapshot=True`` the
    device→host copies start immediately (the async-writer handoff)."""
    canon_p = trainer.ungroup(params)
    canon_o = None
    if opt_state is not None:
        canon_o = type(opt_state)(step=opt_state.step,
                                  m=trainer.ungroup(opt_state.m),
                                  v=trainer.ungroup(opt_state.v))
    if snapshot:
        begin_host_snapshot(canon_p, canon_o)
    return canon_p, canon_o


def _host_arrays(params, opt_state) -> dict:
    """{payload key: host np.ndarray} — the serialization-free snapshot both
    the sync and async writers share."""
    out: dict[str, np.ndarray] = {}
    for name, tree in (("params", params), ("opt", opt_state)):
        if tree is None:
            continue
        for key, leaf in _flatten(tree).items():
            out[f"{name}/{key}"] = np.asarray(jax.device_get(leaf))
    return out


# --------------------------------------------------------------------------
# write path (shared by sync save and the async writer thread)
# --------------------------------------------------------------------------

def _atomic_write(path: pathlib.Path, data: bytes) -> None:
    tmp = path.parent / f".tmp-{path.name}"
    tmp.write_bytes(data)
    tmp.rename(path)                      # atomic on POSIX


def _index_path(directory: pathlib.Path, step: int) -> pathlib.Path:
    return directory / f"step{step:09d}.json"


def _write_step(directory: pathlib.Path, step: int, arrays: dict,
                plan: Optional[ExecutionPlan], keep: int,
                extra_meta: Optional[dict], codec: Optional[str],
                version: int) -> pathlib.Path:
    directory.mkdir(parents=True, exist_ok=True)
    meta = {"step": step,
            "plan": json.loads(plan.to_json()) if plan else None,
            **(extra_meta or {})}

    if version == FORMAT_V1:
        payload = {key: {"dtype": str(arr.dtype), "shape": list(arr.shape),
                         "data": arr.tobytes()}
                   for key, arr in arrays.items()}
        final = directory / f"step{step:09d}.ckpt"
        _atomic_write(final, encode_blob(payload, codec=codec))
    elif version == FORMAT_V2:
        blob_dir = directory / "blobs"
        blob_dir.mkdir(exist_ok=True)
        shards: dict = {}
        for key in sorted(arrays):
            arr = np.ascontiguousarray(arrays[key])
            h = content_hash(arr)         # buffer protocol — no bytes copy
            shards[key] = {"blob": h, "dtype": str(arr.dtype),
                           "shape": list(arr.shape), "nbytes": int(arr.nbytes)}
            blob_path = blob_dir / f"{h}.gvck"
            if not blob_path.exists():    # content-addressed dedup: an
                _atomic_write(blob_path,  # unchanged leaf is hashed, not copied
                              encode_shard(arr.tobytes(), codec=codec))
        meta = {"format": FORMAT_V2, "shards": shards, **meta}
        final = _index_path(directory, step)
    else:
        raise ValueError(f"unknown checkpoint write version {version}")

    _atomic_write(_index_path(directory, step),
                  json.dumps(meta, indent=2, sort_keys=True).encode("utf-8"))
    _atomic_write(directory / "MANIFEST",
                  json.dumps({"latest_step": step}).encode("utf-8"))
    _gc(directory, keep)
    return final


def save(
    directory: str | pathlib.Path,
    step: int,
    params: Any,
    opt_state: Any = None,
    plan: Optional[ExecutionPlan] = None,
    *,
    keep: int = 3,
    extra_meta: Optional[dict] = None,
    codec: Optional[str] = None,           # None = auto (zstd → zlib → raw)
    version: int = FORMAT_V2,              # v1 = single-file (compat writer)
) -> pathlib.Path:
    """Synchronous save — blocks for the full device_get + compress + write.
    The async path (:class:`CheckpointWriter`) produces byte-identical
    output; this stays the oracle and the simple-cases entry point."""
    return _write_step(pathlib.Path(directory), step,
                       _host_arrays(params, opt_state), plan, keep,
                       extra_meta, codec, version)


# --------------------------------------------------------------------------
# GC: step retention + index-aware blob refcounting
# --------------------------------------------------------------------------

def _step_ids(directory: pathlib.Path) -> list[int]:
    steps = {int(p.stem[4:]) for p in directory.glob("step*.ckpt")}
    steps |= {int(p.stem[4:]) for p in directory.glob("step*.json")}
    return sorted(steps)


def _gc(directory: pathlib.Path, keep: int):
    """Drop all but the newest ``keep`` steps, then remove every shard blob
    no surviving step index references (refcounting GC: a blob shared by
    several steps lives until the last one goes)."""
    for old in _step_ids(directory)[:-keep] if keep > 0 else []:
        (directory / f"step{old:09d}.ckpt").unlink(missing_ok=True)
        _index_path(directory, old).unlink(missing_ok=True)
    blob_dir = directory / "blobs"
    if not blob_dir.is_dir():
        return
    live: set[str] = set()
    for step in _step_ids(directory):
        try:
            meta = json.loads(_index_path(directory, step).read_text())
        except (OSError, ValueError):
            continue                      # v1 step without/with bad sidecar
        if meta.get("format") == FORMAT_V2:
            live |= {rec["blob"] for rec in meta["shards"].values()}
    for blob in blob_dir.glob("*.gvck"):
        if blob.stem not in live:
            blob.unlink(missing_ok=True)


def latest_step(directory: str | pathlib.Path) -> Optional[int]:
    manifest = pathlib.Path(directory) / "MANIFEST"
    if not manifest.exists():
        return None
    return int(json.loads(manifest.read_text())["latest_step"])


# --------------------------------------------------------------------------
# async writer
# --------------------------------------------------------------------------

class CheckpointWriter:
    """Double-buffered background checkpoint writer.

    ``save_async`` snapshots the state non-blockingly (device→host copies
    start immediately via :func:`begin_host_snapshot`; the array *values*
    are pinned because the leaf references ride the job) and enqueues the
    hash/compress/write work onto a single writer thread.  The queue is
    bounded at ``max_pending`` (default 1), so the step loop only ever
    blocks when the *previous* save is still in flight — classic double
    buffering.  ``wait()`` drains the queue and re-raises any writer-thread
    error; ``close()`` additionally stops the thread.  Usable as a context
    manager.

    Note: the caller must not donate/delete the snapshotted buffers before
    the write lands (the training drivers run their step with
    ``donate=False`` for exactly this reason).

    ``sink`` (a ``repro.obs`` RunSink-shaped object) receives one ``ckpt``
    event per save — phase ``queued`` with the step-loop stall this save
    cost and the queue depth, phase ``written`` from the writer thread when
    the artifact lands.
    """

    def __init__(self, max_pending: int = 1, *, sink=None):
        self._queue: queue.Queue = queue.Queue(maxsize=max(max_pending, 1))
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self._last_path: Optional[pathlib.Path] = None
        self._stop = object()              # sentinel
        self._sink = sink
        self.blocked_seconds = 0.0         # cumulative step-loop stall time
        self.saves_started = 0
        self.saves_completed = 0

    # ------------------------------------------------------------ internals
    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._worker,
                                            name="ckpt-writer", daemon=True)
            self._thread.start()

    def _worker(self):
        while True:
            job = self._queue.get()
            try:
                if job is self._stop:
                    return
                directory, step, trees, kw = job
                t0 = time.perf_counter()
                path = _write_step(directory, step,
                                   _host_arrays(*trees), **kw)
                with self._lock:
                    self._last_path = path
                    self.saves_completed += 1
                if self._sink is not None:
                    self._sink.emit("ckpt", phase="written", step=step,
                                    write_seconds=time.perf_counter() - t0,
                                    path=str(path))
            except BaseException as e:  # noqa: BLE001 — surfaced on wait()
                with self._lock:
                    if self._error is None:
                        self._error = e
            finally:
                self._queue.task_done()

    def _raise_pending(self):
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise RuntimeError("async checkpoint writer failed; state may be "
                               "missing its latest checkpoint") from err

    # ------------------------------------------------------------ public api
    def save_async(
        self,
        directory: str | pathlib.Path,
        step: int,
        params: Any,
        opt_state: Any = None,
        plan: Optional[ExecutionPlan] = None,
        *,
        keep: int = 3,
        extra_meta: Optional[dict] = None,
        codec: Optional[str] = None,
        version: int = FORMAT_V2,
    ) -> None:
        """Queue a save.  Returns as soon as the snapshot is initiated and a
        writer slot is free — i.e. blocks only on the previous save."""
        self._raise_pending()
        t0 = time.perf_counter()
        begin_host_snapshot(params, opt_state)
        job = (pathlib.Path(directory), step,
               (_pin_host_leaves(params), _pin_host_leaves(opt_state)),
               dict(plan=plan, keep=keep, extra_meta=extra_meta,
                    codec=codec, version=version))
        self._ensure_thread()
        self._queue.put(job)               # blocks iff previous still pending
        self.saves_started += 1
        stalled = time.perf_counter() - t0
        self.blocked_seconds += stalled
        if self._sink is not None:
            self._sink.emit("ckpt", phase="queued", step=step,
                            stall_seconds=stalled,
                            queue_depth=self.queue_depth)

    @property
    def queue_depth(self) -> int:
        """Saves currently queued behind the writer thread."""
        return self._queue.qsize()

    def wait(self) -> Optional[pathlib.Path]:
        """Drain every queued save; raise the first writer error if any.
        Returns the path of the newest completed step artifact."""
        self._queue.join()
        self._raise_pending()
        with self._lock:
            return self._last_path

    def close(self) -> Optional[pathlib.Path]:
        """Drain, stop the writer thread, and return the last written path.
        The writer is reusable after close (a new thread spins up lazily)."""
        try:
            path = self.wait()
        finally:
            if self._thread is not None and self._thread.is_alive():
                self._queue.put(self._stop)
                self._thread.join()
            self._thread = None
        return path

    def __enter__(self) -> "CheckpointWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.close()
        else:                              # don't mask the caller's exception
            try:
                self.close()
            except Exception:
                pass


# --------------------------------------------------------------------------
# restore
# --------------------------------------------------------------------------

class _ShardReader:
    """payload[key] accessor over a v2 step index: decompresses each unique
    blob once even when many leaves share it (dedup makes that common)."""

    def __init__(self, directory: pathlib.Path, meta: dict):
        self._blob_dir = directory / "blobs"
        self._shards = meta["shards"]
        self._cache: dict[str, bytes] = {}

    def __getitem__(self, key: str) -> dict:
        rec = self._shards[key]
        h = rec["blob"]
        if h not in self._cache:
            path = self._blob_dir / f"{h}.gvck"
            if not path.exists():
                raise FileNotFoundError(
                    f"checkpoint shard {h} (leaf {key!r}) is missing from "
                    f"{self._blob_dir} — blob store GC'd or partially copied?")
            raw = decode_shard(path.read_bytes())
            if len(raw) != rec["nbytes"] or content_hash(raw) != h:
                raise CorruptCheckpointError(
                    f"checkpoint shard {h} (leaf {key!r}) fails its content "
                    "hash — corrupt or truncated blob store")
            self._cache[h] = raw
        return {"dtype": rec["dtype"], "shape": rec["shape"],
                "data": self._cache[h]}


def restore(
    directory: str | pathlib.Path,
    step: Optional[int] = None,
    *,
    params_like: Any = None,           # pytree template (abstract ok)
    opt_like: Any = None,
    shardings: Any = None,             # optional matching sharding pytree
    opt_shardings: Any = None,         # same, for the optimizer state
) -> dict:
    """Returns {"step", "params", "opt", "plan"}.  With ``shardings`` /
    ``opt_shardings`` given, leaves are device_put directly onto the
    (possibly new) mesh.  Reads every on-disk format: v2 sharded, v1
    single-file, and legacy pre-header."""
    directory = pathlib.Path(directory)
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {directory}")
    meta = json.loads(_index_path(directory, step).read_text())
    if meta.get("format") == FORMAT_V2:
        payload: Any = _ShardReader(directory, meta)
    else:
        payload = decode_blob((directory / f"step{step:09d}.ckpt").read_bytes())

    def rebuild(prefix: str, like):
        paths, treedef = jax.tree_util.tree_flatten_with_path(like)
        ordered = []
        for path, _ in paths:
            rec = payload[f"{prefix}/{_path_key(path)}"]
            ordered.append(np.frombuffer(rec["data"], dtype=rec["dtype"])
                           .reshape(rec["shape"]))
        return jax.tree_util.tree_unflatten(treedef, ordered)

    result: dict = {"step": step, "plan": None}
    if meta.get("plan"):
        result["plan"] = ExecutionPlan.from_json(json.dumps(meta["plan"]))
    if params_like is not None:
        params = rebuild("params", params_like)
        if shardings is not None:
            params = jax.device_put(params, shardings)
        result["params"] = params
    if opt_like is not None:
        opt = rebuild("opt", opt_like)
        if opt_shardings is not None:
            opt = jax.device_put(opt, opt_shardings)
        result["opt"] = opt
    return result
