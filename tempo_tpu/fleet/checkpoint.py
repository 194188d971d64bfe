"""Tenant device-state checkpoint/restore through the object store.

A checkpoint is ONE blob per tenant: for every registry family the
active series' label rows (as interner ids + the interner's string
table) and the family's device plane rows (gathered through the page
table for paged tenants, sliced for dense ones), plus the spanmetrics
sketch sidecar rows and their metadata. The paged layout (PR 8) is what
makes this cheap — a snapshot is backed pages, not capacity-sized
planes — and the moments tier (PR 9) is what makes it mergeable:
~15 floats/series whose combine is an elementwise add (+ max for the
two bound columns).

Restore is a MERGE, not an overwrite: label rows re-intern into the
live registry, slots allocate through the normal series-table path
(budget- and page-backed, so restore can never overcommit state the
tenant couldn't have allocated live), and plane rows scatter-ADD into
the device state (set for gauges — last-wins semantics). Restoring into
a fresh instance is therefore bit-identical (add-to-zero), and
restoring into an instance that already took in-flight deltas during a
handoff window merges exactly like the cross-shard sketch combine.
Sketch compatibility is enforced by the existing ValueError-raising
merge guards (`sketches._merge_check`, `moments.merge_meta_check`)
before any row is written.

Wire format: `np.savez_compressed` (zip of .npy members, no pickle)
with a single JSON metadata member — readable by anything that can open
a zip, versioned for forward evolution.
"""

from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import logging
import time
import urllib.parse

import numpy as np

from tempo_tpu.backend.raw import DoesNotExist, KeyPath, RawReader, RawWriter
from tempo_tpu.fleet import STATS

_LOG = logging.getLogger("tempo_tpu.fleet")

CHECKPOINT_VERSION = 1
CHECKPOINT_SUFFIX = ".ckpt"
_META_KEY = "__meta__"


class CheckpointMismatch(ValueError):
    """The checkpoint was cut under an incompatible tenant config
    (overrides fingerprint / family shapes / sketch metadata). Restoring
    it would corrupt state, so the caller must skip it loudly."""


# ---------------------------------------------------------------------------
# fingerprint: the config surface a checkpoint's state layout depends on
# ---------------------------------------------------------------------------

def overrides_fingerprint(inst, *, _compact_state: bool = False) -> str:
    """Stable digest of everything that shapes this tenant's series/plane
    layout. A checkpoint cut under different overrides (capacity, label
    dimensions, histogram edges, sketch tier/params) must not merge."""
    reg = inst.registry
    sm = inst.cfg.spanmetrics
    doc = {
        "max_active_series": reg.overrides.max_active_series,
        "external_labels": sorted(reg.overrides.external_labels.items()),
        "processors": sorted(inst.processors),
        "spanmetrics": {
            "dimensions": list(sm.dimensions),
            "intrinsic_dimensions": list(sm.intrinsic_dimensions),
            "histogram_buckets": [float(e) for e in sm.histogram_buckets],
            "sketch": sm.sketch,
            "enable_quantile_sketch": bool(sm.enable_quantile_sketch),
            "sketch_rel_err": float(sm.sketch_rel_err),
            "sketch_min_s": float(sm.sketch_min_s),
            "sketch_max_s": float(sm.sketch_max_s),
            "sketch_max_series": int(sm.sketch_max_series),
            "moments_k": int(sm.moments_k),
            "enable_target_info": bool(sm.enable_target_info),
            # the compact-state tier (int32 grids, a bf16 sum pair) is
            # gone; its key stays in the digest, because every checkpoint
            # cut before carries it and those cut with `false` must keep
            # restoring. `true` is digested only by `restore_instance`,
            # to name the removed tier when it refuses such a checkpoint
            "compact_state": _compact_state,
        },
    }
    if "trace-analytics" in inst.processors:
        # conditional: tenants without the processor keep the exact
        # fingerprints their pre-analytics checkpoints carry
        ta = inst.cfg.traceanalytics
        doc["traceanalytics"] = {
            "enable_latency_share_sketch":
                bool(ta.enable_latency_share_sketch),
            "moments_k": int(ta.moments_k),
            "sketch_max_series": int(ta.sketch_max_series),
            "share_min": float(ta.share_min),
            "share_max": float(ta.share_max),
        }
    raw = json.dumps(doc, sort_keys=True).encode()
    return hashlib.sha256(raw).hexdigest()[:16]


# ---------------------------------------------------------------------------
# family plane access (dense + paged)
# ---------------------------------------------------------------------------

def _family_kind(mt) -> str:
    from tempo_tpu.registry.registry import (Counter, Gauge, Histogram,
                                             NativeHistogram)
    if isinstance(mt, Histogram):
        return "histogram"
    if isinstance(mt, NativeHistogram):
        return "native"
    if isinstance(mt, Gauge):
        return "gauge"
    if isinstance(mt, Counter):
        return "counter"
    raise CheckpointMismatch(f"unknown family type {type(mt).__name__}")


_KIND_ROLES = {
    "counter": ("values",),
    "gauge": ("values",),
    "histogram": ("buckets", "sums", "counts"),
    "native": ("hist", "sums", "counts", "zeros"),
}


def _pad_slots(slots: np.ndarray) -> np.ndarray:
    from tempo_tpu.registry.registry import _pad_len
    padded = np.full(_pad_len(max(slots.size, 1)), -1, np.int32)
    padded[:slots.size] = slots
    return padded


def _family_rows(mt, slots: np.ndarray) -> dict[str, np.ndarray]:
    """{role: [n(, width)] host rows} for the active slots. Caller holds
    the registry state lock (paged gathers ride shared donated arenas)."""
    kind = _family_kind(mt)
    if hasattr(mt, "planes"):            # paged family
        padded = _pad_slots(slots)
        return {role: np.asarray(mt.planes[role].gather(padded)[:slots.size])
                for role in _KIND_ROLES[kind]}
    st = mt.state
    if kind == "counter" or kind == "gauge":
        return {"values": np.asarray(st.values)[slots]}
    if kind == "histogram":
        return {"buckets": np.asarray(st.bucket_counts)[slots],
                "sums": np.asarray(st.sums)[slots],
                "counts": np.asarray(st.counts)[slots]}
    return {"hist": np.asarray(st.hist.counts)[slots],
            "sums": np.asarray(st.sums)[slots],
            "counts": np.asarray(st.counts)[slots],
            "zeros": np.asarray(st.zeros)[slots]}


def _paged_phys(plane, slots: np.ndarray) -> np.ndarray:
    """Arena row index per slot through the host page map (restore runs
    right after ensure_slot backed these pages)."""
    shift = plane.pool.page_shift
    pages = plane.page_map[slots >> shift].astype(np.int64)
    if (pages < 0).any():                # pragma: no cover — ensure_slot ran
        raise CheckpointMismatch("restore hit an unbacked page")
    return (pages << shift) | (slots & (plane.pool.page_rows - 1))


def _plane_scatter(plane, slots: np.ndarray, rows: np.ndarray,
                   op: str = "add") -> None:
    """Merge host rows into a paged plane (caller holds the pool lock)."""
    phys = _paged_phys(plane, slots)
    data = plane.data
    vals = rows.astype(data.dtype) if str(rows.dtype) != str(data.dtype) \
        else rows
    if op == "add":
        plane.rebind(data.at[phys].add(vals))
    elif op == "max":
        plane.rebind(data.at[phys].max(vals))
    else:
        plane.rebind(data.at[phys].set(vals))


def _family_restore(mt, slots: np.ndarray, rows: dict[str, np.ndarray]
                    ) -> None:
    """Scatter-merge checkpoint rows into the family's device planes.
    Count-like planes ADD, so merge order never matters; gauges SET —
    last-write-wins in RESTORE order, so a checkpoint restored into an
    instance that already took newer live samples overwrites them until
    the next sample lands (gauges carry no per-slot timestamp to order
    by). Caller holds the registry state lock."""
    kind = _family_kind(mt)
    if hasattr(mt, "planes"):            # paged family
        for role in _KIND_ROLES[kind]:
            _plane_scatter(mt.planes[role], slots, rows[role],
                           op="set" if kind == "gauge" else "add")
        return
    st = mt.state
    s = np.asarray(slots, np.int32)
    if kind == "counter":
        mt.state = dataclasses.replace(
            st, values=st.values.at[s].add(rows["values"]))
    elif kind == "gauge":
        mt.state = dataclasses.replace(
            st, values=st.values.at[s].set(rows["values"]))
    elif kind == "histogram":
        mt.state = dataclasses.replace(
            st,
            bucket_counts=st.bucket_counts.at[s].add(rows["buckets"]),
            sums=st.sums.at[s].add(rows["sums"]),
            counts=st.counts.at[s].add(rows["counts"]))
    else:
        mt.state = dataclasses.replace(
            st,
            hist=dataclasses.replace(
                st.hist, counts=st.hist.counts.at[s].add(rows["hist"])),
            sums=st.sums.at[s].add(rows["sums"]),
            counts=st.counts.at[s].add(rows["counts"]),
            zeros=st.zeros.at[s].add(rows["zeros"]))


# ---------------------------------------------------------------------------
# snapshot / restore
# ---------------------------------------------------------------------------

def snapshot_instance(inst) -> bytes:
    """One tenant's full metric state as a checkpoint blob.

    Drains the device scheduler first (the drain barrier: updates
    accepted before the snapshot must be IN it — the same barrier the
    collection tick uses), then gathers every family's active rows under
    the registry state lock so the cut is consistent across the
    slot-aligned families and their sketch sidecars.

    CALLER CONTRACT: no push may be in flight on this instance — the
    handoff path fences with `wait_pushes_idle` after `pop_instance`,
    and the shutdown path joins HTTP handler threads first. The WAL
    watermark read below claims every record appended so far; a push
    racing this function could scatter+append between the watermark
    read and the state gather, landing in the blob AND above the
    watermark — double-applied on crash recovery."""
    t0 = time.perf_counter()
    inst.drain()
    reg = inst.registry
    arrays: dict[str, np.ndarray] = {}
    # ingest-WAL watermark map {member instance_id: [segment, seq]}:
    # restored watermarks carry forward (a blob that passed through
    # another member still bounds THIS member's local replay) and the
    # live watermark is read here — after the caller's push fence, so
    # every record whose scatter this snapshot gathered is covered.
    # The caller truncates segments <= checkpointed_wal_seq once the
    # blob write lands.
    wal_meta = {k: [int(v[0]), int(v[1])]
                for k, v in getattr(inst, "wal_watermarks", {}).items()}
    mark = getattr(inst, "_wal_mark", None)
    if mark is not None:
        iid, seg, seq = mark()
        wal_meta[iid] = [int(seg), int(seq)]
        inst.checkpointed_wal_seq = int(seq)
    meta: dict = {
        "version": CHECKPOINT_VERSION,
        "tenant": inst.tenant,
        "created_ts": reg.now(),
        "fingerprint": overrides_fingerprint(inst),
        "layout": inst.state_layout,
        "wal": wal_meta,
        "families": {},
        "spanmetrics": None,
    }
    with reg.state_lock:
        snap = reg.interner.snapshot()
        # one slots/keys resolve per TABLE: share_table-merged trios
        # (spanmetrics, servicegraphs edges) must not triple the key
        # payload or re-run lookup_or_create on identical rows
        tables: dict[int, dict] = {}
        for name, mt in reg._metrics.items():
            t = tables.get(id(mt.table))
            if t is None:
                slots = mt.table.active_slots()
                t = tables[id(mt.table)] = {
                    "owner": name, "slots": slots,
                    "keys": mt.table.slot_keys[slots]}
            kind = _family_kind(mt)
            meta["families"][name] = {
                "kind": kind,
                "label_names": list(mt.label_names),
                "n": int(t["slots"].size),
                "roles": list(_KIND_ROLES[kind]),
                "keys_of": t["owner"],
            }
            for role, rows in _family_rows(mt, t["slots"]).items():
                arrays[f"{name}::{role}"] = rows
        # ship ONLY the strings the checkpointed keys reference, with
        # keys remapped to indices into that list: the full interner
        # table holds every string the tenant EVER saw (purged series
        # included), and restoring it would grow blobs and the receiving
        # member's interner monotonically across handoffs
        if tables:
            ref = np.unique(np.concatenate(
                [t["keys"].ravel() for t in tables.values()]))
        else:
            ref = np.zeros(0, np.int64)
        meta["strings"] = [snap[int(i)] for i in ref]
        for t in tables.values():
            arrays[f"{t['owner']}::keys"] = np.searchsorted(
                ref, t["keys"]).astype(np.int32)
        for proc in inst.processors.values():
            fn = getattr(proc, "sketch_checkpoint", None)
            if fn is None:
                continue
            calls_slots = proc.calls.table.active_slots()
            smeta, srows = fn(calls_slots)
            if smeta is None:
                continue
            meta["spanmetrics"] = smeta
            meta["spanmetrics"]["family"] = proc.calls.name
            for k, v in srows.items():
                arrays[f"__sketch__::{k}"] = v
        # processor-keyed aux sidecars (generalized sketch slot): any
        # processor exposing aux_checkpoint ships slot-aligned planes
        # tied to one family's active-slot order (trace-analytics
        # latency-share moments ride here)
        for pname, proc in inst.processors.items():
            fn = getattr(proc, "aux_checkpoint", None)
            if fn is None:
                continue
            fam = proc.aux_family()
            ameta, arows = fn(fam.table.active_slots())
            if ameta is None:
                continue
            ameta["family"] = fam.name
            meta.setdefault("aux", {})[pname] = ameta
            for k, v in arows.items():
                arrays[f"__aux__::{pname}::{k}"] = v
    blob = _encode(meta, arrays)
    STATS["checkpoint_seconds"] += time.perf_counter() - t0
    STATS["checkpoint_bytes"] += len(blob)
    STATS["checkpoints"] += 1
    return blob


def restore_instance(inst, blob: bytes) -> dict:
    """Merge a checkpoint into a live (possibly fresh, possibly already
    ingesting) tenant instance; returns {"series", "dropped"} counts.

    Raises CheckpointMismatch (a ValueError) when the checkpoint's
    fingerprint, family layout, or sketch metadata is incompatible —
    the same guard discipline as the cross-shard sketch merges."""
    meta, arrays = _decode(blob)
    if meta.get("version") != CHECKPOINT_VERSION:
        raise CheckpointMismatch(
            f"checkpoint version {meta.get('version')} != "
            f"{CHECKPOINT_VERSION}")
    reg = inst.registry
    want_fp = overrides_fingerprint(inst)
    if meta.get("fingerprint") != want_fp:
        if meta.get("fingerprint") == overrides_fingerprint(
                inst, _compact_state=True):
            raise CheckpointMismatch(
                "checkpoint was cut under generator.spanmetrics."
                "compact_state: true, a state tier that was removed: "
                "its int32 grids and bf16 sum pairs do not merge into "
                "f32 planes")
        raise CheckpointMismatch(
            f"overrides fingerprint {meta.get('fingerprint')} does not "
            f"match this instance's {want_fp} (tenant config changed "
            "since the checkpoint was cut)")
    # sketch metadata guards run BEFORE any row is written: a half-merged
    # tenant is worse than a refused checkpoint
    sk_proc = None
    if meta.get("spanmetrics") is not None:
        for proc in inst.processors.values():
            if getattr(proc, "sketch_restore", None) is not None:
                sk_proc = proc
                proc.sketch_meta_check(meta["spanmetrics"])  # ValueError
                break
        if sk_proc is None:
            raise CheckpointMismatch(
                "checkpoint carries sketch planes but this instance has "
                "no span-metrics processor")
    # aux guards follow the same no-write-before-validation discipline
    aux_meta = meta.get("aux") or {}
    aux_procs: dict = {}
    for pname, ameta in aux_meta.items():
        proc = inst.processors.get(pname)
        if proc is None or getattr(proc, "aux_restore", None) is None:
            raise CheckpointMismatch(
                f"checkpoint carries aux planes for processor {pname!r} "
                "which is not enabled on this instance")
        proc.aux_meta_check(ameta)  # ValueError on layout mismatch
        aux_procs[pname] = proc
    strings = meta.get("strings", [])
    idmap = reg.interner.intern_many(strings) if strings \
        else np.zeros(0, np.int32)
    stats = {"series": 0, "dropped": 0}
    now = reg.now()
    with reg.state_lock:
        # per-family layout guards run BEFORE any row is written too:
        # the fingerprint narrows the config surface but does not cover
        # every family's label layout (e.g. a processor whose dimension
        # config lives outside it), and a half-merged tenant is worse
        # than a refused checkpoint
        for name, fam in meta["families"].items():
            mt = reg._metrics.get(name)
            if mt is None:
                _LOG.warning("fleet restore %s: family %s not present "
                             "live — skipped", inst.tenant, name)
                continue
            if tuple(fam["label_names"]) != mt.label_names or \
                    fam["kind"] != _family_kind(mt):
                raise CheckpointMismatch(
                    f"family {name}: checkpoint layout "
                    f"({fam['kind']}, {fam['label_names']}) != live "
                    f"({_family_kind(mt)}, {list(mt.label_names)})")
        calls_live_slots = None
        calls_ok = None
        aux_slots: dict = {}  # processor name -> (slots, ok) of its family
        resolved: dict[str, tuple] = {}  # keys_of -> (slots, ok)
        for name, fam in meta["families"].items():
            mt = reg._metrics.get(name)
            if mt is None:
                continue
            n = int(fam["n"])
            if n == 0:
                continue
            owner = fam.get("keys_of", name)
            got = resolved.get(owner)
            if got is None:
                # one lookup_or_create per shared table — the series
                # budget debits once for the slot-aligned trio, like live
                keys = arrays[f"{owner}::keys"]
                live_rows = np.ascontiguousarray(idmap[keys], np.int32)
                slots = mt.table.lookup_or_create(live_rows, now)
                ok = slots >= 0
                got = resolved[owner] = (slots, ok)
                dropped = int(n - ok.sum())
                if dropped:
                    # budget/page exhaustion mid-restore: surviving
                    # series still merge (the budget gate behaves
                    # exactly as live)
                    stats["dropped"] += dropped
                stats["series"] += int(ok.sum())
            slots, ok = got
            rows = {role: arrays[f"{name}::{role}"][ok]
                    for role in fam["roles"]}
            _family_restore(mt, slots[ok], rows)
            if sk_proc is not None and name == sk_proc.calls.name:
                calls_live_slots, calls_ok = slots, ok
            for pname in aux_procs:
                if name == aux_meta[pname]["family"]:
                    aux_slots[pname] = (slots, ok)
        if sk_proc is not None and calls_live_slots is not None:
            srows = {k[len("__sketch__::"):]: v for k, v in arrays.items()
                     if k.startswith("__sketch__::")}
            sk_proc.sketch_restore(meta["spanmetrics"], calls_live_slots,
                                   calls_ok, srows)
        for pname, proc in aux_procs.items():
            got = aux_slots.get(pname)
            if got is None:
                continue  # anchor family empty in the blob: nothing to merge
            prefix = f"__aux__::{pname}::"
            arows = {k[len(prefix):]: v for k, v in arrays.items()
                     if k.startswith(prefix)}
            proc.aux_restore(aux_meta[pname], got[0], got[1], arows)
    # merge WAL watermarks (max seq per member): the local replay must
    # skip records this blob's lineage already holds
    marks = getattr(inst, "wal_watermarks", None)
    if marks is not None:
        for iid, wm in (meta.get("wal") or {}).items():
            cur = marks.get(iid)
            if cur is None or int(wm[1]) > int(cur[1]):
                marks[iid] = [int(wm[0]), int(wm[1])]
    STATS["restores"] += 1
    STATS["restore_merged_series"] += stats["series"]
    STATS["restore_dropped_series"] += stats["dropped"]
    return stats


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

def _encode(meta: dict, arrays: dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    payload = {_META_KEY: np.frombuffer(
        json.dumps(meta).encode(), np.uint8)}
    for k, v in arrays.items():
        v = np.asarray(v)
        if v.dtype not in (np.float32, np.float64, np.int32, np.int64):
            v = v.astype(np.float32)     # bf16 etc. normalize at the wire
        payload[k] = v
    np.savez_compressed(buf, **payload)
    return buf.getvalue()


def _decode(blob: bytes) -> tuple[dict, dict[str, np.ndarray]]:
    with np.load(io.BytesIO(blob), allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files if k != _META_KEY}
        meta = json.loads(bytes(z[_META_KEY].tobytes()).decode())
    return meta, arrays


# ---------------------------------------------------------------------------
# object-store layout: <prefix>/<quoted tenant>/<ts>-<instance>.ckpt
# ---------------------------------------------------------------------------

def _tenant_seg(tenant: str) -> str:
    return urllib.parse.quote(tenant, safe="")


def checkpoint_name(now: float, instance_id: str) -> str:
    # zero-padded nanoseconds sort lexically = chronologically; the
    # writer id makes concurrent cuts collision-free
    return (f"{int(now * 1e9):020d}-"
            f"{urllib.parse.quote(instance_id, safe='')}{CHECKPOINT_SUFFIX}")


def write_checkpoint(writer: RawWriter, prefix: str, tenant: str,
                     blob: bytes, name: str) -> None:
    from tempo_tpu.utils import faults
    if faults.ARMED:
        faults.fire("fleet.checkpoint.write")
    writer.write(name, KeyPath((prefix, _tenant_seg(tenant))), blob)


def list_checkpoints(reader: RawReader, prefix: str
                     ) -> dict[str, list[str]]:
    """{tenant: sorted checkpoint object names} under the prefix."""
    out: dict[str, list[str]] = {}
    try:
        found = reader.find(KeyPath((prefix,)), CHECKPOINT_SUFFIX)
    except (DoesNotExist, FileNotFoundError):
        return out
    for rel in found:
        rel = rel.replace("\\", "/")
        if "/" not in rel:
            continue
        seg, name = rel.rsplit("/", 1)
        out.setdefault(urllib.parse.unquote(seg), []).append(name)
    for names in out.values():
        names.sort()
    return out


def read_checkpoint(reader: RawReader, prefix: str, tenant: str,
                    name: str) -> bytes:
    return reader.read(name, KeyPath((prefix, _tenant_seg(tenant))))


def delete_checkpoint(writer: RawWriter, prefix: str, tenant: str,
                      name: str) -> None:
    writer.delete(name, KeyPath((prefix, _tenant_seg(tenant))))


# -- store-side consumed markers --------------------------------------------
#
# Restore is a scatter-ADD, so replaying a blob double-counts every
# count-kind series. A marker object written AFTER the merge lands and
# BEFORE the blob's delete makes consumption visible to EVERY process:
# a member that crashed mid-delete, or a peer whose stale ring view
# claims the same tenant, sees the marker and deletes instead of
# re-restoring. Marker-first ordering means a crash can strand a tiny
# marker object (never a replayable blob); the consumed-cleanup path
# deletes both. Markers don't end in CHECKPOINT_SUFFIX, so
# list_checkpoints never surfaces them as blobs. The remaining hole is
# two members reading the same blob before EITHER writes its marker —
# closing that needs store-side leases, out of scope here.

CONSUMED_SUFFIX = ".consumed"


def mark_consumed(writer: RawWriter, prefix: str, tenant: str,
                  name: str) -> None:
    writer.write(name + CONSUMED_SUFFIX,
                 KeyPath((prefix, _tenant_seg(tenant))), b"1")


def is_consumed(reader: RawReader, prefix: str, tenant: str,
                name: str) -> bool:
    try:
        reader.read(name + CONSUMED_SUFFIX,
                    KeyPath((prefix, _tenant_seg(tenant))))
        return True
    except (DoesNotExist, FileNotFoundError):
        return False


def delete_consumed_marker(writer: RawWriter, prefix: str, tenant: str,
                           name: str) -> None:
    writer.delete(name + CONSUMED_SUFFIX,
                  KeyPath((prefix, _tenant_seg(tenant))))
