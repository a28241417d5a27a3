"""Checkpointing through the version store.

Checkpoints are first-class *versioned data*: every leaf is streamed into the
annex as a ``.npy`` artifact (content-addressed — unchanged leaves across
steps deduplicate to the same annex key for free) and the worktree records a
pointer, plus a manifest, committed with a machine-actionable record whose
originating :class:`~repro.core.spec.RunSpec` is embedded in the commit
object. This gives the paper's properties to training state: a checkpoint IS
a commit hash; lineage is the commit DAG; a clone knows every checkpoint and
fetches only the one it restores.

Delta dedup (DESIGN.md §12): leaves above the repository's chunk threshold
go through the content-defined chunking tier, so a multi-step campaign where
only a few percent of each tensor changes per step ingests only the changed
chunks — per-step bytes scale with churn, not state size. The save path is a
single streamed pass (npy header + contiguous array slices fed straight into
``AnnexStore.put_stream``); whole-leaf serializations are never staged in
memory. Restore resolves every leaf key from the manifest, finds what is
already local with one batched ``has_many``, delta-fetches only missing
chunks, and reassembles leaves on a thread pool so concurrent streams split
the striped filesystem's aggregate bandwidth (§9).

Fault tolerance: ``restore`` after a crash/preemption resumes from the
newest checkpoint commit; with deterministic data + optimizer the resumed
run is bitwise identical (tested). A crash between leaf publication and the
commit (``ckpt:leaves-written``) leaves only unreferenced annex objects —
``Session.gc()`` sweeps orphaned chunks; the commit either exists entirely
or not at all. Elastic restarts pass a different ``mesh``/``shardings`` —
leaves are re-``device_put`` under the new layout. Async mode runs
host-transfer + file IO + commit on a background thread so the train loop
only blocks for the on-device snapshot; a failure on the worker is re-raised
from ``wait()`` (or the next ``save_async``), never swallowed.
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import threading
from multiprocessing.pool import ThreadPool

import jax
import ml_dtypes
import numpy as np

from .. import obs
from ..core.annex import make_pointer
from ..core.hashing import make_annex_key
from ..core.records import RunRecord
from ..core.repo import Repository
from ..core.spec import RunSpec

MARKER = "[REPRO CKPT]"

_BLOCK = 1 << 20  # streaming quantum for leaf serialization


def _flatten(tree, prefix=""):
    out = {}
    for k in sorted(tree):
        v = tree[k]
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(_flatten(v, path))
        else:
            out[path] = v
    return out


def _unflatten(flat: dict):
    root: dict = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return root


def _npy_header(raw: np.ndarray) -> bytes:
    """The exact ``np.save`` prelude (magic + format-1.0 header) for
    ``raw``, so streamed leaves are bit-identical to an ``np.save`` file."""
    buf = io.BytesIO()
    np.lib.format.write_array_header_1_0(
        buf, np.lib.format.header_data_from_array_1_0(raw)
    )
    header = buf.getvalue()
    magic = np.lib.format.magic(1, 0)
    # numpy >= 2.0 emits the magic from write_array_header_1_0 itself;
    # older versions leave it to the caller
    if not header.startswith(magic):
        header = magic + header
    return header


def _raw(arr: np.ndarray) -> np.ndarray:
    """The C-ordered array whose npy serialization stores ``arr``."""
    raw = arr
    if arr.dtype == ml_dtypes.bfloat16:  # numpy can't serialize bf16
        raw = arr.view(np.uint16)
    if not raw.flags.c_contiguous:
        # ascontiguousarray would also promote 0-d to 1-d; only copy when
        # the buffer really isn't C-order
        raw = np.ascontiguousarray(raw)
    return raw


def leaf_keys(state) -> dict[str, str]:
    """{leaf path: the annex key an unchunked save stores it under} — a
    content digest that equals the manifest's ``key`` exactly when the leaf
    is bit-identical to the saved one."""
    out = {}
    for path, v in _flatten(state).items():
        raw = _raw(np.asarray(jax.device_get(v)))
        header = _npy_header(raw)
        h = hashlib.sha256()
        for block in _npy_stream(header, raw):
            h.update(block)
        out[path] = make_annex_key(h.hexdigest(), len(header) + raw.nbytes)
    return out


def _npy_stream(header: bytes, raw: np.ndarray, block: int = _BLOCK):
    """Yield an npy serialization as bounded blocks: the header, then
    contiguous slices of the array's own buffer — the whole-file bytes are
    never materialized."""
    yield header
    if raw.nbytes == 0:
        return
    mv = (
        memoryview(raw).cast("B")
        if raw.ndim
        else memoryview(raw.tobytes())  # 0-d: a few bytes, copy is fine
    )
    for i in range(0, raw.nbytes, block):
        yield mv[i : i + block]


def _watch_ready(step: int, arrays: list):
    """A thread holding the span ``repro.ckpt.restore.ready`` open from now
    until every array in ``arrays`` is ready on its device. The caller never
    waits for it."""
    def watch():
        with obs.span("repro.ckpt.restore.ready", step=step):
            try:
                jax.block_until_ready(arrays)
            except jax.errors.JaxRuntimeError:
                pass  # a leaf already donated to a step, which waits for it

    thread = threading.Thread(target=watch, name="ckpt-restore-ready", daemon=True)
    thread.start()
    return thread


class CheckpointManager:
    def __init__(
        self,
        repo: Repository,
        subdir: str = "checkpoints",
        fetch_workers: int = 8,
    ):
        self.repo = repo
        self.subdir = subdir
        self.fetch_workers = fetch_workers
        self._thread: threading.Thread | None = None
        self._ready: threading.Thread | None = None  # the last restore's watcher
        self._async_exc: BaseException | None = None
        # checkpoints() cache, per branch: ref tip the entries were computed
        # at, every commit oid already walked, and the (ts, oid, step) rows
        self._ckpt_cache: dict[str, dict] = {}

    # ------------------------------------------------------------- save
    def save(
        self,
        step: int,
        params,
        opt_state,
        data_step: int = 0,
        extra: dict | None = None,
        message: str = "",
    ) -> str:
        host = self._snapshot(step, params, opt_state)
        return self._write(step, host, data_step, extra, message)

    def save_async(self, step, params, opt_state, data_step=0, extra=None,
                   message: str = "") -> None:
        """Snapshot on-device state, then write+commit on a worker thread.
        A failure of the previous async save is re-raised here (and from
        :meth:`wait`) — it is never silently dropped."""
        self.wait()
        host = self._snapshot(step, params, opt_state)

        def work():
            try:
                self._write(step, host, data_step, extra, message)
            except BaseException as e:  # incl. simulated crashes
                self._async_exc = e

        self._thread = threading.Thread(target=work)
        self._thread.start()

    def _snapshot(self, step, params, opt_state) -> dict:
        """The state's leaves copied to the host, flat by path."""
        flat = _flatten({"params": params, "opt_state": opt_state})
        with obs.span("repro.ckpt.snapshot", step=step,
                      bytes=sum(v.nbytes for v in flat.values())):
            return {p: np.asarray(jax.device_get(v)) for p, v in flat.items()}

    def wait(self) -> None:
        """Block until the in-flight async save completes; re-raise its
        failure, if any."""
        if self._thread is not None:
            with obs.span("repro.ckpt.wait"):
                self._thread.join()
            self._thread = None
        if self._ready is not None:  # long done: the loop has used the state
            self._ready.join()
            self._ready = None
        exc, self._async_exc = self._async_exc, None
        if exc is not None:
            raise exc

    def _write(self, step, host: dict, data_step, extra, message) -> str:
        with obs.span("repro.ckpt.write", step=step,
                      bytes=sum(a.nbytes for a in host.values())):
            reldir = f"{self.subdir}/step_{step:08d}"
            absdir = os.path.join(self.repo.root, reldir)
            fs = self.repo.fs
            manifest = {"step": step, "data_step": data_step, "leaves": {},
                        "extra": extra or {}}
            for path, arr in host.items():
                fname = path.replace("/", ".") + ".npy"
                dtype_name = str(arr.dtype)
                raw = _raw(arr)
                header = _npy_header(raw)
                chunked = self.repo._should_chunk(len(header) + raw.nbytes)
                key = self.repo.annex.put_stream(
                    _npy_stream(header, raw), chunked=chunked
                )
                fs.write_bytes(
                    os.path.join(absdir, fname), make_pointer(key, chunked=chunked)
                )
                manifest["leaves"][path] = {
                    "file": fname,
                    "shape": list(arr.shape),
                    "dtype": dtype_name,
                    "key": key,
                    "chunked": chunked,
                }
            fs.write_bytes(
                os.path.join(absdir, "manifest.json"),
                json.dumps(manifest, indent=1, sort_keys=True).encode(),
            )
            # §10 crash matrix: a crash here leaves published leaves/chunks but
            # no commit — recovery sees zero divergence, gc sweeps the orphans
            fs.crash_point("ckpt:leaves-written")
            spec = RunSpec(cmd=f"checkpoint --step {step}", outputs=(reldir,))
            record = RunRecord(
                cmd=spec.cmd,
                dsid=self.repo.dsid,
                outputs=[reldir],
                extras={"checkpoint_step": step, "data_step": data_step,
                        **(extra or {})},
            )
            msg = message or f"{MARKER} step {step}"
            if MARKER not in msg:
                msg = f"{MARKER} {msg}"
            with obs.span("repro.ckpt.commit", step=step):
                oid = self.repo.save(
                    paths=[reldir], message=record.to_message(msg),
                    spec=spec.to_json(),
                )
            fs.crash_point("ckpt:after-commit")
            return oid

    # ---------------------------------------------------------- restore
    def _walk(self, head: str, seen: set, old_head: str | None):
        """Walk ancestry from ``head``, stopping at already-seen commits.
        Returns (new (ts, oid, step) rows, whether ``old_head`` was reached)
        — reaching it proves the update was append-only, so the cached rows
        are still exactly the checkpoints reachable from ``head``."""
        touched = old_head is None
        out = []
        frontier = [head]
        while frontier:
            oid = frontier.pop()
            if oid == old_head:
                touched = True
            if oid in seen:
                continue
            seen.add(oid)
            c = self.repo.objects.get_commit(oid)
            if MARKER in c["message"]:
                rec = RunRecord.from_message(c["message"])
                if rec and "checkpoint_step" in rec.extras:
                    out.append(
                        (c["timestamp"], oid, rec.extras["checkpoint_step"])
                    )
            frontier.extend(c["parents"])
        return out, touched

    def checkpoints(self) -> list[tuple[str, int]]:
        """(commit, step) for every checkpoint commit, newest first.

        Cached by ref tip: an unchanged HEAD answers from the cache, an
        advanced HEAD walks only the commits added since the last call — so
        ``latest()`` inside a long campaign is O(new commits), not a re-scan
        of the whole log per save. A rewritten history (reset/amend, where
        the new tip's ancestry never meets the cached tip) rebuilds from
        scratch."""
        head = self.repo.head_commit()
        if head is None:
            return []
        branch = self.repo.current_branch()
        cache = self._ckpt_cache.get(branch)
        if cache is not None and cache["head"] == head:
            return [(oid, s) for _, oid, s in cache["entries"]]
        if cache is None:
            cache = {"head": None, "seen": set(), "entries": []}
        new, touched = self._walk(head, cache["seen"], cache["head"])
        if not touched:
            cache = {"head": None, "seen": set(), "entries": []}
            new, _ = self._walk(head, cache["seen"], None)
        entries = sorted(cache["entries"] + new, key=lambda e: (-e[0], -e[2]))
        cache.update(head=head, entries=entries)
        self._ckpt_cache[branch] = cache
        return [(oid, s) for _, oid, s in entries]

    def latest(self) -> tuple[str, int] | None:
        cps = self.checkpoints()
        return cps[0] if cps else None

    def _tree_bytes(self, oid: str, rel: str) -> bytes:
        """Read one committed file's content straight from the object store
        / annex — no worktree checkout."""
        entry = self.repo.entry_at(oid, rel)
        if entry is None:
            raise FileNotFoundError(f"{rel} not in commit {oid}")
        if entry["t"] == "blob":
            return self.repo.objects.get_blob(entry["oid"])
        self.repo.annex_fetch_key(
            entry["key"], chunked=bool(entry.get("chunked"))
        )
        return self.repo.annex.read(entry["key"])

    def manifest(self, commitish: str | None = None):
        """(commit oid, manifest) of a checkpoint commit — the newest one by
        default; (None, None) when there is none. Reads no leaf."""
        if commitish is None:
            latest = self.latest()
            if latest is None:
                return None, None
            commitish = latest[0]
        oid = self.repo.resolve(commitish)
        rec = RunRecord.from_message(
            self.repo.objects.get_commit(oid)["message"]
        )
        step = rec.extras["checkpoint_step"]
        reldir = f"{self.subdir}/step_{step:08d}"
        return oid, json.loads(self._tree_bytes(oid, f"{reldir}/manifest.json"))

    def restore(self, commitish: str | None = None, shardings=None,
                fetch_workers: int | None = None, subtree: str | None = None):
        """Returns (state_tree, manifest). ``shardings``: optional pytree (or
        flat {path: sharding}) to device_put leaves under — this is the
        elastic-resume path (different mesh than at save time).
        ``subtree`` (e.g. ``"params"``) reads only the leaves under it, so
        serving never fetches optimizer moments.

        Leaves are resolved to annex keys from the manifest, a batched
        ``has_many`` finds what is already local, missing keys delta-fetch
        (only chunks not shared with already-restored checkpoints move), and
        reassembly runs on ``fetch_workers`` threads so concurrent read
        streams split the aggregate bandwidth (§9)."""
        with obs.span("repro.ckpt.restore") as span:
            state, manifest = self._restore(commitish, shardings, fetch_workers, subtree)
            if manifest is None:
                return None, None
            leaves = jax.tree.leaves(state)
            span.set_metadata(step=manifest["step"], bytes=sum(a.nbytes for a in leaves))
        self._ready = _watch_ready(manifest["step"], leaves)
        return state, manifest

    def _restore(self, commitish, shardings, fetch_workers, subtree):
        oid, manifest = self.manifest(commitish)
        if manifest is None:
            return None, None
        reldir = f"{self.subdir}/step_{manifest['step']:08d}"
        leaves = {
            path: meta for path, meta in manifest["leaves"].items()
            if subtree is None or path.startswith(subtree + "/")
        }
        # resolve each leaf to an annex key; legacy checkpoints (no "key" in
        # the manifest) fall back to the committed tree entry, where small
        # leaves may be inline blobs
        jobs: dict[str, tuple] = {}
        for path, meta in leaves.items():
            key = meta.get("key")
            chunked = bool(meta.get("chunked"))
            if key is None:
                entry = self.repo.entry_at(oid, f"{reldir}/{meta['file']}")
                if entry is None:
                    raise FileNotFoundError(
                        f"{reldir}/{meta['file']} not in commit {oid}"
                    )
                if entry["t"] == "annex":
                    key, chunked = entry["key"], bool(entry.get("chunked"))
                else:
                    jobs[path] = ("blob", entry["oid"])
                    continue
            jobs[path] = ("key", key, chunked)
        keys = [j[1] for j in jobs.values() if j[0] == "key"]
        local = self.repo.annex.has_many(keys)

        def fetch(item):
            path, job = item
            if job[0] == "blob":
                data = self.repo.objects.get_blob(job[1])
            else:
                _, key, chunked = job
                if key not in local:
                    self.repo.annex_fetch_key(key, chunked=chunked)
                data = self.repo.annex.read(key)
            return path, np.load(io.BytesIO(data))

        items = list(jobs.items())
        workers = fetch_workers if fetch_workers is not None else self.fetch_workers
        with obs.span("repro.ckpt.restore.fetch"):
            if workers > 1 and len(items) > 1:
                with ThreadPool(min(workers, len(items))) as pool:
                    loaded = pool.map(fetch, items)
            else:
                loaded = [fetch(it) for it in items]
        arrays = dict(loaded)
        flat_shardings = (
            _flatten(shardings) if isinstance(shardings, dict) else None
        )
        flat = {}
        for path, meta in leaves.items():
            arr = arrays[path]
            if meta["dtype"] == "bfloat16":
                arr = arr.view(ml_dtypes.bfloat16)
            if flat_shardings is not None and path in flat_shardings:
                flat[path] = jax.device_put(arr, flat_shardings[path])
            else:
                flat[path] = jax.numpy.asarray(arr)
        return _unflatten(flat), manifest
