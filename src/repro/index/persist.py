"""Durable snapshots + crash recovery for ShardedUHNSW (DESIGN.md §9).

A snapshot is an atomic, manifest-based dump of the whole index state:
per-segment graph topology (`GraphArrays` leaves), the frozen data matrix,
the global-id maps, query params, the remembered build method, and the
delta-buffer contents at save time. It is written with the
write-tmp/fsync/rename idiom — a crash
mid-write leaves only a `.tmp` directory that loaders never look at — and
every array file carries a CRC32 recorded in the manifest, so a *torn*
snapshot (post-crash corruption, partial copy) is detected and skipped,
never loaded.

Recovery composes the snapshot with the delta write-ahead log
(`repro.index.wal`):

    recover(dir) = load newest durable snapshot
                 + replay the durable prefix of every WAL segment

Replay re-runs each logged insert through `ShardedUHNSW.add`, so a
compaction that happened in the crashed process is *re-derived* during
replay (segment builds are deterministic: same vectors, same seed, same
remembered build method). Records whose global id is already frozen in the
snapshot are skipped (idempotence guard); a replay that would *skip past*
an id (a lost WAL segment) raises `RecoveryError` instead of silently
dropping inserts. The result is bit-identical — ids and distances — to the
index a never-crashed process would hold, at every p (tests/test_persist).

`DurableIndex` packages the lifecycle: WAL-append before every insert,
snapshot rotation at compaction (the delta is empty right then, so the
snapshot is the cheap full-frozen dump the compaction already paid for),
and pruning that always keeps enough history to fall back one snapshot.
"""

from __future__ import annotations

import json
import os
import shutil
import warnings
import zlib
from dataclasses import asdict, fields
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.core.bulk_build import DeviceGraph
from repro.core.hnsw import GraphArrays
from repro.core.uhnsw import UHNSWParams
from repro.index.segment import SegmentedGraphs
from repro.index.sharded import ShardedUHNSW
from repro.index.wal import WriteAheadLog, list_wals, replay, wal_path

SNAPSHOT_PREFIX = "snapshot_"
SNAPSHOT_FORMAT = 1


class SnapshotError(RuntimeError):
    """A snapshot directory is structurally invalid or fails its CRC."""


class RecoveryError(RuntimeError):
    """Recovery cannot reach a consistent state (e.g. a WAL id gap)."""


def snapshot_path(directory, seq: int) -> Path:
    return Path(directory) / f"{SNAPSHOT_PREFIX}{seq:08d}"


def list_snapshots(directory) -> list[tuple[int, Path]]:
    """All committed snapshot dirs (tmp excluded), ascending by sequence."""
    directory = Path(directory)
    if not directory.exists():
        return []
    out = []
    for p in directory.iterdir():
        if p.is_dir() and p.name.startswith(SNAPSHOT_PREFIX) \
                and not p.name.endswith(".tmp"):
            try:
                out.append((int(p.name[len(SNAPSHOT_PREFIX):]), p))
            except ValueError:
                continue
    return sorted(out)


def _fsync_write(path: Path, data: bytes):
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


def _graph_meta(g) -> dict:
    arrays = GraphArrays.from_graph(g)
    return {
        "metric_p": float(arrays.metric_p),
        "m": int(g.m),
        "m0": int(g.m0),
        "entry_point": int(np.asarray(arrays.entry)),
        "n": int(arrays.n),
        "n_levels": len(arrays.upper_adj),
    }


def _graph_arrays_items(prefix: str, g):
    arrays = GraphArrays.from_graph(g)
    yield f"{prefix}.adj0", np.asarray(arrays.adj0)
    for l, (adj, g2l) in enumerate(zip(arrays.upper_adj, arrays.upper_g2l)):
        yield f"{prefix}.up{l}", np.asarray(adj)
        yield f"{prefix}.g2l{l}", np.asarray(g2l)
    levels = getattr(g, "levels", None)
    if levels is not None:
        yield f"{prefix}.levels", np.asarray(levels)


def save_snapshot(index: ShardedUHNSW, directory, seq: int | None = None,
                  ) -> Path:
    """Write one atomic snapshot of `index` as snapshot_<seq>.

    seq defaults to one past the newest committed snapshot. The manifest is
    written last (fsync'd), then the directory renames into place — the
    rename is the commit point.

    On-disk layout: `<dir>/snapshot_<seq:08d>/{manifest.json, arrays.npz}`.
    The npz holds `X` ((n, d) f32 frozen rows), per-segment
    `s<i:04d>.{ids,g1.*,g2.*}` graph arrays (int32/int64 exactly as the
    `GraphArrays` leaves), `delta.{vecs,ids}` ((c, d) f32 / (c,) int64),
    and — when a compressed band exists or `params.compressed_band` is
    set — `band.{codes,scale,radius,perm}` ((n, d) int8, 3x (d,) f32/
    int32; DESIGN.md §10). The manifest duplicates the band's energy
    permutation (`band.perm`) so operators can inspect it without
    unpacking arrays. Failure modes: a crash before the final rename
    leaves only a `.tmp` directory loaders ignore; a crash after it
    leaves a fully durable snapshot (rename is atomic on POSIX).
    """
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    if seq is None:
        snaps = list_snapshots(directory)
        seq = snaps[-1][0] + 1 if snaps else 0
    final = snapshot_path(directory, seq)
    tmp = directory / (final.name + ".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    seg = index.segments
    payload: dict[str, np.ndarray] = {"X": index._X_host}
    seg_meta = []
    for i, (g1, g2, ids) in enumerate(
            zip(seg.graphs1, seg.graphs2, seg.global_ids)):
        pref = f"s{i:04d}"
        payload[f"{pref}.ids"] = np.asarray(ids, dtype=np.int64)
        for key, arr in _graph_arrays_items(f"{pref}.g1", g1):
            payload[key] = arr
        for key, arr in _graph_arrays_items(f"{pref}.g2", g2):
            payload[key] = arr
        seg_meta.append({"n": int(g1.n), "g1": _graph_meta(g1),
                         "g2": _graph_meta(g2)})
    delta_vecs, delta_ids = index.delta.vectors(), index.delta.ids()
    payload["delta.vecs"] = delta_vecs
    payload["delta.ids"] = delta_ids.astype(np.int64)

    # compressed storage band (DESIGN.md §10): persisted whenever the
    # params ask for it (force-built here if no query has yet) or one was
    # already built — recovery then skips the quantization pass and the
    # energy permutation survives in the manifest alongside the arrays
    band = index._band
    if band is None and index.params.compressed_band:
        band = index.compressed_band()
    band_meta = None
    if band is not None:
        payload["band.codes"] = np.asarray(band.codes)
        payload["band.scale"] = np.asarray(band.scale)
        payload["band.radius"] = np.asarray(band.radius)
        payload["band.perm"] = np.asarray(band.perm)
        band_meta = {"n": band.n, "d": band.d,
                     "perm": np.asarray(band.perm).tolist()}

    arrays_file = tmp / "arrays.npz"
    np.savez(arrays_file, **payload)
    with open(arrays_file, "rb") as f:
        os.fsync(f.fileno())
    raw = arrays_file.read_bytes()
    manifest = {
        "format": SNAPSHOT_FORMAT,
        "kind": "uhnsw-sharded",
        "seq": int(seq),
        "next_id": int(index._next_id),
        "delta_capacity": int(index.delta.capacity),
        "delta_count": int(len(index.delta)),
        "build_method": index._build_method,
        "params": asdict(index.params),
        "d": int(index.dim),
        "segments": seg_meta,
        "band": band_meta,
        "arrays": {"file": "arrays.npz", "crc32": zlib.crc32(raw),
                   "size": len(raw)},
    }
    _fsync_write(tmp / "manifest.json", json.dumps(manifest).encode())
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)  # atomic commit
    return final


def read_manifest(path: Path) -> dict:
    """Load + structurally validate one snapshot's manifest, CRC included.

    Raises SnapshotError on any torn/invalid state — callers that want
    fallback semantics use `latest_durable_snapshot`.
    """
    path = Path(path)
    mf = path / "manifest.json"
    try:
        manifest = json.loads(mf.read_text())
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        raise SnapshotError(f"{path}: unreadable manifest ({e})") from e
    if not isinstance(manifest, dict) \
            or manifest.get("format") != SNAPSHOT_FORMAT \
            or manifest.get("kind") != "uhnsw-sharded":
        raise SnapshotError(f"{path}: manifest is not a format-"
                            f"{SNAPSHOT_FORMAT} uhnsw-sharded snapshot")
    info = manifest.get("arrays") or {}
    af = path / str(info.get("file", ""))
    try:
        raw = af.read_bytes()
    except OSError as e:
        raise SnapshotError(f"{path}: missing array file ({e})") from e
    if len(raw) != info.get("size") or zlib.crc32(raw) != info.get("crc32"):
        raise SnapshotError(
            f"{path}: array file failed its CRC/size check — torn snapshot")
    return manifest


def latest_durable_snapshot(directory) -> Path | None:
    """Newest snapshot that passes full validation; torn/invalid newer
    snapshots are skipped with a warning (crash-corruption fallback)."""
    for seq, path in reversed(list_snapshots(directory)):
        try:
            read_manifest(path)
            return path
        except SnapshotError as e:
            warnings.warn(f"skipping non-durable snapshot: {e}",
                          stacklevel=2)
    return None


def _params_from(manifest: dict) -> UHNSWParams:
    known = {f.name for f in fields(UHNSWParams)}
    kw = {k: v for k, v in (manifest.get("params") or {}).items()
          if k in known}
    return UHNSWParams(**kw)


def _load_graph(npz, prefix: str, meta: dict, data: np.ndarray) -> DeviceGraph:
    n = meta["n"]
    upper_adj, upper_g2l = [], []
    for l in range(meta["n_levels"]):
        upper_adj.append(jnp.asarray(npz[f"{prefix}.up{l}"]))
        upper_g2l.append(jnp.asarray(npz[f"{prefix}.g2l{l}"]))
    arrays = GraphArrays(
        adj0=jnp.asarray(npz[f"{prefix}.adj0"]),
        upper_adj=tuple(upper_adj),
        upper_g2l=tuple(upper_g2l),
        entry=jnp.asarray(meta["entry_point"], dtype=jnp.int32),
        n=n,
        metric_p=float(meta["metric_p"]),
    )
    lv_key = f"{prefix}.levels"
    levels = npz[lv_key] if lv_key in getattr(npz, "files", ()) else None
    return DeviceGraph(
        metric_p=float(meta["metric_p"]), m=int(meta["m"]),
        m0=int(meta["m0"]), entry_point=int(meta["entry_point"]),
        max_level=meta["n_levels"], levels=levels, data=data, arrays=arrays,
    )


def load_snapshot(path, params: UHNSWParams | None = None) -> ShardedUHNSW:
    """Reconstruct a ShardedUHNSW from one snapshot directory.

    The rebuilt index is bit-identical to the saved one: the per-segment
    `GraphArrays` round-trip exactly (the restack re-pads the same inputs
    to the same envelope), the data matrix is byte-preserved, the
    delta contents saved with the snapshot are restored verbatim, and a
    persisted compressed band (DESIGN.md §10) is reattached byte-for-byte
    — no re-quantization pass on the recovery path (an index saved
    *without* a band lazily rebuilds one on first use; `build_band` is
    deterministic, so either route lands on identical bytes).

    `params` overrides the saved UHNSWParams (the manifest copy is
    filtered against the current dataclass fields, so snapshots written
    before a param existed load with its default). Raises SnapshotError
    via `read_manifest` on a torn/invalid snapshot.
    """
    path = Path(path)
    manifest = read_manifest(path)
    npz = np.load(path / manifest["arrays"]["file"])
    X = np.ascontiguousarray(npz["X"], dtype=np.float32)
    graphs1, graphs2, global_ids = [], [], []
    for i, meta in enumerate(manifest["segments"]):
        pref = f"s{i:04d}"
        ids = np.asarray(npz[f"{pref}.ids"], dtype=np.int64)
        data = np.ascontiguousarray(X[ids])
        graphs1.append(_load_graph(npz, f"{pref}.g1", meta["g1"], data))
        graphs2.append(_load_graph(npz, f"{pref}.g2", meta["g2"], data))
        global_ids.append(ids)
    segments = SegmentedGraphs(graphs1=graphs1, graphs2=graphs2,
                               global_ids=global_ids)
    idx = ShardedUHNSW(segments, X,
                       params=params or _params_from(manifest),
                       delta_capacity=manifest["delta_capacity"])
    idx._build_method = manifest.get("build_method")
    idx.delta.restore(npz["delta.vecs"], npz["delta.ids"])
    idx._next_id = int(manifest["next_id"])
    assert idx._next_id == len(X) + len(idx.delta), \
        (idx._next_id, len(X), len(idx.delta))
    if "band.codes" in npz.files:
        from repro.index.compressed import CompressedBand

        perm = np.asarray(npz["band.perm"], dtype=np.int32)
        band_meta = manifest.get("band") or {}
        if "perm" in band_meta:  # the manifest copy is authoritative
            mperm = np.asarray(band_meta["perm"], dtype=np.int32)
            assert np.array_equal(mperm, perm), "band perm mismatch"
        idx._band = CompressedBand(
            codes=jnp.asarray(npz["band.codes"]),
            scale=jnp.asarray(npz["band.scale"]),
            radius=jnp.asarray(npz["band.radius"]),
            perm=jnp.asarray(perm),
        )
    return idx


def restore_segment(index, seg: int, directory) -> bool:
    """Restore one quarantined segment's rows from the newest durable
    snapshot (DESIGN.md §11) — the data-plane half of segment recovery.

    Graph topology never goes bad in place (it is immutable after build);
    what poison/corruption hits is the *row storage* — `_X_host`, the
    device copy `X`, the stacked per-segment `segments.X`, and the
    per-graph data arrays the next restack would read. This rewrites all
    four from snapshot bytes that passed the manifest CRC re-verification
    (`read_manifest` — a torn snapshot is never a restore source) and
    drops the §10 band/scan caches, which quantized the poisoned rows.

    The snapshot segment is matched by *global-id equality*, not by
    position: compactions after the snapshot may have appended segments,
    and a segment created after the newest snapshot has no restore source
    at all. Returns True when `seg` was restored; False when there is no
    durable snapshot or none of its segments matches (the caller leaves
    the segment quarantined). Accepts a DurableIndex or a bare
    ShardedUHNSW.

    Re-admission stays with the caller: a restored segment must still
    pass its canary probes (`ShardedUHNSW.canary_probe`) before the
    health tracker returns it to serving.
    """
    index = getattr(index, "index", index)  # unwrap DurableIndex
    snap = latest_durable_snapshot(directory)
    if snap is None:
        return False
    manifest = read_manifest(snap)  # CRC re-verification (commit point)
    npz = np.load(snap / manifest["arrays"]["file"])
    live_ids = np.asarray(index.segments.global_ids[seg], dtype=np.int64)
    for i in range(len(manifest["segments"])):
        ids = np.asarray(npz[f"s{i:04d}.ids"], dtype=np.int64)
        if not np.array_equal(ids, live_ids):
            continue
        rows = np.ascontiguousarray(npz["X"][ids], dtype=np.float32)
        # copy-on-write (mirrors faults.poison_segment): never write into
        # an _X_host that may alias the caller's dataset array
        index._X_host = np.array(index._X_host, dtype=np.float32)
        index._X_host[live_ids] = rows
        index.X = jnp.asarray(index._X_host)
        segs = index.segments
        segs.X = segs.X.at[seg, : len(rows)].set(jnp.asarray(rows))
        # the next compaction restacks from the per-graph data arrays
        segs.graphs1[seg].data = rows
        segs.graphs2[seg].data = rows
        index._band = None        # quantized over the poisoned rows
        index._scan_cache = None
        if index._rt is not None:  # .at[].set dropped the placement
            index.shard_over(index._rt)
        return True
    return False


def recover(directory, params: UHNSWParams | None = None) -> ShardedUHNSW:
    """Newest durable snapshot + durable WAL prefix -> live index.

    Replays every WAL segment in sequence order through `index.add`, so
    mid-log compactions are re-derived deterministically. Records already
    frozen in the snapshot are skipped (id guard); an id *gap* — replay
    would have to invent a missing insert — raises RecoveryError.
    """
    directory = Path(directory)
    snap = latest_durable_snapshot(directory)
    if snap is None:
        raise FileNotFoundError(f"no durable snapshot under {directory}")
    idx = load_snapshot(snap, params=params)
    for seq, path in list_wals(directory):
        batches, clean = replay(path)
        if not clean:
            warnings.warn(f"{path}: torn/corrupt tail — replay stopped at "
                          f"the last durable record", stacklevel=2)
        for ids, vecs in batches:
            for gid, vec in zip(ids, vecs):
                gid = int(gid)
                if gid < idx.n:
                    continue       # already durable in the snapshot
                if gid > idx.n:
                    raise RecoveryError(
                        f"WAL id gap: next insert id is {idx.n} but "
                        f"{path.name} logs id {gid} — a WAL segment is "
                        f"missing; refusing to recover silently")
                idx.add(vec)
    return idx


class DurableIndex:
    """Fault-tolerant lifecycle wrapper around a ShardedUHNSW.

    Every insert is WAL-appended (fsync'd) *before* it touches the index;
    compaction triggers snapshot rotation (new snapshot + fresh WAL
    segment) via the index's `on_compact` hook. Reads and the staged
    search API delegate to the wrapped index, so a DurableIndex drops into
    `UniversalVectorService(index=...)` and `service.insert` rides the WAL
    automatically.

    Args:
      index: the live ShardedUHNSW to wrap (its `on_compact` hook is
        claimed; `close()` releases it).
      directory: snapshot + WAL root; created on first save.
      sync: fsync every WAL append (True, the durable default) or leave
        flushing to the OS (False — faster, loses the tail on power cut).
      keep_snapshots: how many newest snapshots `prune()` retains
        (floored at 1); WALs are kept from one sequence before the
        oldest retained snapshot onward.

    Failure modes: `add`/`add_batch` raise RuntimeError if no WAL is open
    (constructed directly instead of via create/recover); recovery raises
    FileNotFoundError with no durable snapshot and RecoveryError on a WAL
    id gap (see module docstring).
    """

    def __init__(self, index: ShardedUHNSW, directory, sync: bool = True,
                 keep_snapshots: int = 2):
        self.index = index
        self.directory = Path(directory)
        self.sync = sync
        self.keep_snapshots = max(1, int(keep_snapshots))
        snaps = list_snapshots(self.directory)
        self._seq = snaps[-1][0] if snaps else None
        self._wal: WriteAheadLog | None = None
        index.on_compact = self._on_compact

    # -- lifecycle -----------------------------------------------------------

    @classmethod
    def create(cls, index: ShardedUHNSW, directory, sync: bool = True,
               keep_snapshots: int = 2) -> "DurableIndex":
        """Snapshot `index` now and open a WAL for subsequent inserts."""
        dur = cls(index, directory, sync=sync, keep_snapshots=keep_snapshots)
        dur.save()
        return dur

    @classmethod
    def recover(cls, directory, params: UHNSWParams | None = None,
                sync: bool = True, keep_snapshots: int = 2) -> "DurableIndex":
        """Recover from `directory` and re-arm durability: the recovered
        state is immediately re-snapshotted (a fresh durable baseline — a
        WAL with a torn tail is never appended to) and a new WAL opened."""
        idx = recover(directory, params=params)
        return cls.create(idx, directory, sync=sync,
                          keep_snapshots=keep_snapshots)

    def save(self) -> Path:
        """Rotate now: snapshot the current state, open a fresh WAL."""
        seq = 0 if self._seq is None else self._seq + 1
        path = save_snapshot(self.index, self.directory, seq=seq)
        self._seq = seq
        if self._wal is not None:
            self._wal.close()
        self._wal = WriteAheadLog(wal_path(self.directory, seq),
                                  sync=self.sync)
        self.prune()
        return path

    def close(self):
        if self._wal is not None:
            self._wal.close()
            self._wal = None
        if self.index.on_compact == self._on_compact:
            self.index.on_compact = None

    def prune(self):
        """Drop snapshots/WALs no longer needed for fallback recovery.

        Keeps the newest `keep_snapshots` snapshots, and every WAL from
        one sequence *before* the oldest kept snapshot onward — so even if
        the newest snapshot is later found torn, the previous one plus the
        retained WALs still reconstruct the full state (an insert batch
        that straddled a rotation lives in the pre-rotation WAL).
        """
        snaps = list_snapshots(self.directory)
        if len(snaps) > self.keep_snapshots:
            for _, path in snaps[: -self.keep_snapshots]:
                shutil.rmtree(path, ignore_errors=True)
            snaps = snaps[-self.keep_snapshots:]
        if snaps:
            floor = snaps[0][0] - 1
            for seq, path in list_wals(self.directory):
                if seq < floor:
                    path.unlink(missing_ok=True)

    # -- writes --------------------------------------------------------------

    def _on_compact(self):
        self.save()

    def _wal_required(self) -> WriteAheadLog:
        if self._wal is None:
            raise RuntimeError(
                "DurableIndex has no open WAL — construct it with "
                "DurableIndex.create/recover (or call save()) first")
        return self._wal

    def add(self, vec: np.ndarray) -> int:
        """WAL-append, then insert. Durable before it is searchable."""
        wal = self._wal_required()
        gid = self.index.n
        wal.append([gid], np.asarray(vec, np.float32).reshape(1, -1))
        out = self.index.add(vec)
        assert out == gid, (out, gid)
        return out

    def add_batch(self, vecs: np.ndarray) -> list[int]:
        """One fsync for the whole batch (the WAL's amortization unit)."""
        vecs = np.ascontiguousarray(vecs, dtype=np.float32)
        wal = self._wal_required()
        gid0 = self.index.n
        wal.append(np.arange(gid0, gid0 + len(vecs)), vecs)
        return [self.index.add(v) for v in vecs]

    # -- reads delegate to the wrapped index ---------------------------------

    def __getattr__(self, name):
        return getattr(self.index, name)
