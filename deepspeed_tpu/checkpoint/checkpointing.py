"""Checkpoint save/load with DeepSpeed's tag/dir layout semantics.

Analog of ``deepspeed/runtime/engine.py:1149-1416``: a checkpoint directory contains a
``<tag>/`` subdir with ``mp_rank_00_model_states`` (module params + counters + lr/scaler
state) and, under ZeRO, per-DP-shard optimizer state files
``zero_pp_rank_{dp}_mp_rank_{mp}_optim_states`` whose shards can be merged and
re-partitioned when reloading under a different DP world size (elastic checkpoint,
reference stage2.py:1713-1779 / stage1.py:836-947). Arrays are stored as .npz; metadata as
JSON. ``latest`` file tracks the most recent tag (engine.py:1351-1353).

In the single-controller JAX runtime one process owns every shard, so "per-rank files"
are written by slicing the global arrays — the on-disk layout (one optim file per DP rank)
is preserved so multi-host loaders and the elastic merge path work identically.

Durability (docs/resilience.md): a save is a two-phase operation. Phase 1
(``snapshot_checkpoint``) materializes every payload as host data — it runs the
device→host copies and the multi-host collective gathers but touches no files,
so phase 2 (``write_snapshot``) can run on a background thread while training
continues. Phase 2 commits through ``<tag>.tmp/`` + per-file sha256 manifest +
fsync + atomic rename, and ``latest`` is updated via tmp + ``os.replace`` — a
crash at any point leaves either the previous committed state or a ``.tmp``
dir/mismatched manifest that ``verify_checkpoint`` detects and restore skips,
never loads.
"""

import hashlib
import json
import os
import shutil
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import logger


def _path_key(path) -> str:
    """Canonical '/'-joined key for a tree path — the single definition every
    save/load layout (tree npz, per-rank shards, offload regions) keys by."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                    for p in path)


def _needs_allgather(leaf) -> bool:
    """Whether materializing ``leaf`` requires the collective gather. The decision
    derives from the sharding's PROCESS SPAN — a globally consistent property —
    not per-process addressability: an array placed on a subset of processes is
    fully addressable on its owner but not elsewhere, and an addressability-based
    rule would have the owner skip the allgather other processes join (deadlock)."""
    if not isinstance(leaf, jax.Array) or leaf.is_fully_replicated:
        return False
    span = {d.process_index for d in leaf.sharding.device_set}
    return len(span) > 1


def _leaf_to_host(leaf) -> np.ndarray:
    """Host copy of a (possibly multi-host sharded) array. Cross-process sharded
    leaves are gathered collectively — EVERY process must call this on the same
    leaves in the same order (save_checkpoint guarantees it)."""
    if _needs_allgather(leaf):
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
    return np.asarray(jax.device_get(leaf))


def _flatten_with_paths(tree, materialize: bool = True) -> Dict[str, np.ndarray]:
    """``materialize=False`` (non-writer processes): join only the collective
    gathers that cross-process sharded leaves require — skip the redundant D2H of
    every addressable/replicated leaf (N-1 wasted full-model copies otherwise)."""
    flat = {}
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves_with_paths:
        key = _path_key(path)
        if not materialize:
            if _needs_allgather(leaf):
                _leaf_to_host(leaf)  # collective participation only
            continue
        arr = _leaf_to_host(leaf)
        if arr.dtype not in (np.float32, np.float64, np.int32, np.int64, np.bool_,
                             np.uint32, np.uint8, np.int8, np.float16):
            # npz can't natively store ml_dtypes (bfloat16 et al.); widen losslessly.
            arr = arr.astype(np.float32)
        flat[key] = arr
    return flat


def _unflatten_like(template, flat: Dict[str, np.ndarray], numpy: bool = False):
    """``numpy=True`` keeps leaves as host arrays — required for the offload path,
    whose fp32 master+moments may not fit on device at all."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths:
        key = _path_key(path)
        if key not in flat:
            raise KeyError(f"checkpoint missing array {key!r}")
        arr = flat[key]
        if numpy:
            leaves.append(np.asarray(arr, dtype=np.dtype(leaf.dtype)).reshape(leaf.shape))
        else:
            leaves.append(jnp.asarray(arr, dtype=leaf.dtype).reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _load_tree_npz(path: str, template):
    with np.load(path) as data:
        flat = {k: data[k] for k in data.files}
    return _unflatten_like(template, flat)


def _ckpt_dir(save_dir: str, tag: str) -> str:
    return os.path.join(save_dir, str(tag))


# --------------------------------------------------------- commit protocol
# Manifest name is distinct from the offload_manifest_* region manifests: this
# one is the integrity record of the WHOLE tag dir (per-file sha256), written
# last so its presence certifies every other file landed completely.
MANIFEST_NAME = "ds_ckpt_manifest.json"
TMP_SUFFIX = ".tmp"


def _file_sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _fsync_dir(path: str) -> None:
    """fsync the directory entry so renames/creates inside it are durable.
    Best-effort: not every filesystem (or platform) supports dir fds."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _atomic_write_text(path: str, text: str) -> None:
    """tmp-file + fsync + os.replace: readers see the old content or the new
    content, never a torn prefix."""
    tmp = path + TMP_SUFFIX
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(os.path.dirname(path) or ".")


def write_latest(save_dir: str, tag: str) -> None:
    """Atomically point ``latest`` at ``tag`` — a preemption mid-write must
    never leave a torn ``latest`` that fails every future restore."""
    _atomic_write_text(os.path.join(save_dir, "latest"), str(tag))


def write_manifest(ckpt_dir: str, extra: Optional[Dict] = None) -> Dict:
    """Checksum every file in ``ckpt_dir`` into the integrity manifest. Written
    LAST in the commit sequence: a save killed before this point leaves no (or
    a stale) manifest, which verify_checkpoint reports as torn."""
    entries = {}
    for name in sorted(os.listdir(ckpt_dir)):
        path = os.path.join(ckpt_dir, name)
        if name == MANIFEST_NAME or name.endswith(TMP_SUFFIX) \
                or not os.path.isfile(path):
            continue
        entries[name] = {"sha256": _file_sha256(path),
                         "bytes": os.path.getsize(path)}
    manifest = {"version": 1, "files": entries}
    if extra:
        manifest.update(extra)
    _atomic_write_text(os.path.join(ckpt_dir, MANIFEST_NAME),
                       json.dumps(manifest, sort_keys=True))
    return manifest


def verify_checkpoint(ckpt_dir: str):
    """(ok, reason) integrity verdict for one tag dir. A checkpoint whose
    manifest is missing a file, or whose bytes/sha256 disagree with the
    manifest, is TORN — restore must skip it, never load it. Pre-manifest
    (legacy) checkpoints pass with a reason noting the weaker guarantee."""
    if not os.path.isdir(ckpt_dir):
        return False, "missing checkpoint directory"
    mpath = os.path.join(ckpt_dir, MANIFEST_NAME)
    if not os.path.isfile(mpath):
        return True, "legacy (no integrity manifest)"
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (ValueError, OSError) as e:
        return False, f"unreadable manifest ({e})"
    for name, ent in manifest.get("files", {}).items():
        path = os.path.join(ckpt_dir, name)
        if not os.path.isfile(path):
            return False, f"missing file {name}"
        if os.path.getsize(path) != ent.get("bytes"):
            return False, (f"size mismatch in {name}: "
                           f"{os.path.getsize(path)} != {ent.get('bytes')}")
        if _file_sha256(path) != ent.get("sha256"):
            return False, f"checksum mismatch in {name}"
    return True, "ok"


def model_states_name(mp_rank: int = 0) -> str:
    return f"mp_rank_{mp_rank:02d}_model_states"


def optim_states_name(dp_rank: int, mp_rank: int = 0) -> str:
    return f"zero_pp_rank_{dp_rank}_mp_rank_{mp_rank:02d}_optim_states"


def offload_states_name(proc: int) -> str:
    return f"zero_offload_proc_{proc}_optim_states"


def _offload_leaf_keys(off):
    """Leaf path keys in tree_flatten order for the offload class's param tree."""
    skeleton = jax.tree_util.tree_unflatten(off._treedef,
                                            [np.zeros(0)] * len(off._shapes))
    return [_path_key(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(skeleton)[0]]


def _snapshot_offload_regions(engine):
    """Per-PROCESS region payloads for the host-tier state (multi-host safe).

    Each process snapshots only the master/moment regions its devices own
    (``zero_offload_proc_N``); a region manifest records leaf shapes and every
    region's slice so any topology can reassemble full leaves on load — the
    region-wise analog of the reference's per-rank ``zero_pp_rank_N`` files.
    Buffer regions are COPIED: the async writer thread must not observe the
    next step's in-place host updates."""
    off = engine._offload
    keys = _offload_leaf_keys(off)
    shard = {}
    regions_meta = []
    for li, regions in enumerate(off._leaf_regions):
        for r in regions:
            tag = f"r{li}_{r.offset}"
            for prefix, buf in (("master", off.fp32), ("exp_avg", off.exp_avg),
                                ("exp_avg_sq", off.exp_avg_sq)):
                shard[f"{prefix}/{tag}"] = np.array(
                    buf[r.offset:r.offset + r.size])
            regions_meta.append({"tag": tag, "leaf": li,
                                 "starts": [sl.start for sl in r.slices],
                                 "stops": [sl.stop for sl in r.slices]})
    manifest = {"n_procs": jax.process_count(), "proc": jax.process_index(),
                "leaves": [{"key": k, "shape": list(shp)}
                           for k, shp in zip(keys, off._shapes)],
                "regions": regions_meta}
    return shard, manifest


def _save_barrier():
    """Rendezvous across hosts: save_checkpoint returns only after EVERY process's
    files are on disk (an immediate load may otherwise race another host's writes)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("deepspeed_tpu_checkpoint_save")


def _offload_manifests(ckpt_dir: str):
    import glob
    return sorted(glob.glob(os.path.join(ckpt_dir, "offload_manifest_*.json")))


def _load_offload_regions(ckpt_dir: str):
    """Reassemble full master/exp_avg/exp_avg_sq flat dicts (key -> full array) from
    the per-process region files. Topology-agnostic: works for any current dp."""
    out = None
    seen_procs = set()
    n_procs_seen = set()
    for mpath in _offload_manifests(ckpt_dir):
        with open(mpath) as f:
            manifest = json.load(f)
        leaves = manifest["leaves"]
        seen_procs.add(manifest["proc"])
        n_procs_seen.add(manifest["n_procs"])
        if out is None:
            out = {prefix: {l["key"]: np.zeros(l["shape"], np.float32) for l in leaves}
                   for prefix in ("master", "exp_avg", "exp_avg_sq")}
        path = os.path.join(ckpt_dir, offload_states_name(manifest["proc"]) + ".npz")
        with np.load(path) as data:
            for r in manifest["regions"]:
                leaf = leaves[r["leaf"]]
                slices = tuple(slice(a, b) for a, b in zip(r["starts"], r["stops"]))
                shape = tuple(b - a for a, b in zip(r["starts"], r["stops"]))
                for prefix in ("master", "exp_avg", "exp_avg_sq"):
                    out[prefix][leaf["key"]][slices] = \
                        data[f"{prefix}/{r['tag']}"].reshape(shape)
    assert out is not None, "no offload manifests found"
    if len(n_procs_seen) != 1 or seen_procs != set(range(next(iter(n_procs_seen)))):
        # partial saves AND stale manifests from an older topology in a reused tag
        # dir must fail loud, not merge into (or zero out) the restored state
        raise RuntimeError(
            f"offload checkpoint is inconsistent: manifests for processes "
            f"{sorted(seen_procs)} with recorded world sizes {sorted(n_procs_seen)}")
    return out["master"], out["exp_avg"], out["exp_avg_sq"]


def _scatter_offload_regions(ckpt_dir: str, off) -> bool:
    """Same-topology fast path: copy saved regions straight into the LOCAL offload
    buffers without materializing full trees (each host allocates only its partition
    — full-tree reassembly of a multi-B model would 3x-overshoot a host sized for the
    partitioned steady state). Returns False when the topology changed (any local
    region unmatched) — caller falls back to full reassembly."""
    local = {}
    for li, regions in enumerate(off._leaf_regions):
        for r in regions:
            key = (li, tuple(sl.start for sl in r.slices),
                   tuple(sl.stop for sl in r.slices))
            local[key] = r
    bufs = {"master": off.fp32, "exp_avg": off.exp_avg, "exp_avg_sq": off.exp_avg_sq}
    matched = set()
    for mpath in _offload_manifests(ckpt_dir):
        with open(mpath) as f:
            manifest = json.load(f)
        if len(manifest["leaves"]) != len(off._shapes) or any(
                tuple(l["shape"]) != tuple(shp)
                for l, shp in zip(manifest["leaves"], off._shapes)):
            return False  # different model/tree
        hits = []
        for r in manifest["regions"]:
            key = (r["leaf"], tuple(r["starts"]), tuple(r["stops"]))
            if key in local:
                hits.append((r, local[key]))
        if not hits:
            continue
        path = os.path.join(ckpt_dir, offload_states_name(manifest["proc"]) + ".npz")
        with np.load(path) as data:
            for saved, lr in hits:
                for prefix, buf in bufs.items():
                    buf[lr.offset:lr.offset + lr.size] = \
                        data[f"{prefix}/{saved['tag']}"]
                matched.add((saved["leaf"], tuple(saved["starts"]),
                             tuple(saved["stops"])))
    return matched == set(local.keys())


def comm_ef_geometry(engine):
    """Geometry descriptor of the engine-held compressed-exchange error-feedback
    buffers (``_comm_we``/``_comm_se``), or None when the engine holds none.
    This is what save records next to the buffers and what restore validates
    (resilience/elastic.py) — the chunk→global-offset map is a function of
    (dp, slice_size) and, under bucketed overlap, of the per-bucket leaf
    partition, so a restore must prove it can replay the same layout (or a
    remappable resize of it) before touching the buffers."""
    if getattr(engine, "_comm_we", None) is None:
        return None
    topo = engine._comm_topo
    plan = getattr(engine, "_overlap_plan", None)
    geo = {"dp": int(engine.dp_size), "slice_size": int(topo.slice_size)}
    if plan is not None:
        geo["layout"] = "bucketed"
        geo["buckets"] = [{"sizes": [int(s) for s in b["sizes"]],
                           "n": int(b["n"]), "n_pad": int(b["n_pad"])}
                          for b in plan]
    else:
        from ..comm.hierarchical import padded_size, tree_size
        n_total = tree_size(engine.params)
        geo["layout"] = "monolithic"
        geo["n"] = int(n_total)
        geo["n_pad"] = int(padded_size(n_total, engine.dp_size))
    return geo


def snapshot_checkpoint(engine, tag: Optional[str] = None, client_state: Dict = {}):
    """Phase 1 of a save: materialize every checkpoint payload as HOST data.

    Runs the device→host copies (and the multi-host collective gathers every
    process must join) but touches NO files — the returned snapshot is
    self-contained host state, so phase 2 (``write_snapshot``) can run on a
    background writer thread while training keeps stepping
    (resilience/async_ckpt.py). The step programs donate their state buffers,
    but device_get copies to host before the next step runs, so the snapshot
    can never observe a half-updated tree."""
    if tag is None:
        tag = f"global_step{engine.global_steps}"
    offload = getattr(engine, "_offload", None)
    # Multi-host: the model-states/scaler/optim-shard/latest files are shared paths —
    # exactly one WRITER (process 0), or concurrent identical-path np.savez calls
    # corrupt the archives. But cross-process sharded state (ZeRO masters, a
    # pipe-sharded wte) needs a collective gather that EVERY process participates in,
    # so ALL processes run every flatten below (offload included — no early return
    # before the last flatten) and only the payload retention is gated.
    writer = jax.process_index() == 0
    files: Dict[str, Any] = {}  # filename -> ("npz", flat dict) | ("json", obj)

    if offload is not None:
        # host-tier state: each process snapshots its own regions (multi-host safe)
        shard, off_manifest = _snapshot_offload_regions(engine)
        proc = jax.process_index()
        files[offload_states_name(proc) + ".npz"] = ("npz", shard)
        files[f"offload_manifest_{proc}.json"] = ("json", off_manifest)

    # --- model states (replicated compute params + host-side counters) ---
    # _ckpt_export: engines with a non-canonical runtime layout (SPMD pipeline's
    # pipe-stacked stages) serialize in the layer-keyed form so checkpoints stay
    # portable across stage counts / executor modes
    params_flat = _flatten_with_paths(engine._ckpt_export(engine.params, "params"),
                                      materialize=writer)
    if writer:
        files[model_states_name() + ".npz"] = ("npz", params_flat)
    meta = {
        "global_steps": engine.global_steps,
        "micro_steps": engine.micro_steps,
        "skipped_steps": engine.skipped_steps,
        "dp_world_size": engine.dp_size,
        "zero_stage": engine.zero_optimization_stage(),
        "optimizer_name": engine.optimizer.name,
        "param_groups": [
            {k: (list(v) if isinstance(v, tuple) else v) for k, v in g.items()}
            for g in engine.optimizer.param_groups
        ],
        "lr_scheduler": engine.lr_scheduler.state_dict() if engine.lr_scheduler is not None else None,
        "client_state": client_state,
    }
    if writer:
        files[model_states_name() + ".json"] = ("json", meta)

    # --- scaler state ---
    scaler_flat = _flatten_with_paths(engine.scaler_state, materialize=writer)
    if writer:
        files["loss_scaler.npz"] = ("npz", scaler_flat)

    if offload is None:
        # --- optimizer + master states, one file per DP rank (elastic layout) ---
        from ..runtime.zero.sharding import elastic_split
        dp = engine.dp_size
        master_flat = _flatten_with_paths(
            engine._ckpt_export(engine.master_params, "master"), materialize=writer)
        opt_flat = _flatten_with_paths(engine._ckpt_export(engine.opt_state, "opt"),
                                       materialize=writer)
        if writer:
            split = {f"{prefix}/{key}": elastic_split(arr, dp)
                     for prefix, flat in (("master", master_flat), ("opt", opt_flat))
                     for key, arr in flat.items()}
            for dp_rank in range(dp):
                files[optim_states_name(dp_rank) + ".npz"] = (
                    "npz", {key: parts[dp_rank] for key, parts in split.items()})
            # shape manifest for elastic restore
            shapes = {f"master/{k}": list(v.shape) for k, v in master_flat.items()}
            shapes.update({f"opt/{k}": list(v.shape) for k, v in opt_flat.items()})
            files["optim_shapes.json"] = ("json", {"dp_world_size": dp,
                                                  "shapes": shapes})

    # --- engine-held compressed-comm error feedback (docs/resilience.md) ---
    ef_geo = comm_ef_geometry(engine)
    if ef_geo is not None:
        ef_flat = _flatten_with_paths({"server_error": engine._comm_se,
                                       "worker_error": engine._comm_we},
                                      materialize=writer)
        if writer:
            files["comm_ef.npz"] = ("npz", ef_flat)
            files["comm_ef.json"] = ("json", ef_geo)

    return {"tag": str(tag), "writer": writer,
            "single_process": jax.process_count() == 1,
            "offload": offload is not None,
            "n_procs": jax.process_count(),
            "manifest_meta": {"tag": str(tag),
                              "global_steps": int(engine.global_steps),
                              "dp_world_size": int(engine.dp_size)},
            "files": files}


def _write_payloads(dirpath: str, files: Dict[str, Any]) -> None:
    for name in sorted(files):
        kind, payload = files[name]
        path = os.path.join(dirpath, name)
        if kind == "npz":
            with open(path, "wb") as f:
                np.savez(f, **payload)
                f.flush()
                os.fsync(f.fileno())
        else:
            with open(path, "w") as f:
                json.dump(payload, f)
                f.flush()
                os.fsync(f.fileno())


def write_snapshot(snapshot: Dict, save_dir: str, save_latest: bool = True) -> str:
    """Phase 2 of a save: the commit protocol. Pure host file I/O — no device
    access — so it is safe on a background writer thread.

    Single-process: every file lands in ``<tag>.tmp/``, the integrity manifest
    is written (itself via tmp + replace), everything is fsynced, and the tmp
    dir is atomically renamed to ``<tag>/``. A crash at ANY point leaves
    either the previous committed state or a ``.tmp`` dir restore ignores.

    Multi-process: each process writes its own files straight into the final
    dir (a cross-host dir rename cannot be made atomic without another
    rendezvous); after the barrier, process 0 writes the manifest LAST, so a
    torn multi-host save still presents as missing/mismatched manifest and is
    skipped at restore. ``latest`` always updates via tmp + os.replace."""
    tag = snapshot["tag"]
    files = snapshot["files"]
    final_dir = _ckpt_dir(save_dir, tag)
    os.makedirs(save_dir, exist_ok=True)

    if snapshot["single_process"]:
        tmp_dir = final_dir + TMP_SUFFIX
        if os.path.isdir(tmp_dir):
            shutil.rmtree(tmp_dir)
        os.makedirs(tmp_dir)
        _write_payloads(tmp_dir, files)
        write_manifest(tmp_dir, extra=snapshot["manifest_meta"])
        _fsync_dir(tmp_dir)
        if os.path.isdir(final_dir):
            # re-saving an existing tag: the old dir must vacate the name. The
            # crash window between rmtree and rename can lose THIS tag, but
            # ``latest`` still points at a committed tag until the final step.
            shutil.rmtree(final_dir)
        os.rename(tmp_dir, final_dir)
        _fsync_dir(save_dir)
    else:
        os.makedirs(final_dir, exist_ok=True)
        if snapshot["offload"] and snapshot["writer"]:
            # a reused tag dir may hold files from an older, larger topology;
            # current writers only touch indices < n_procs, so this is safe
            import glob as _glob
            for stale in _glob.glob(os.path.join(final_dir, "offload_manifest_*.json")):
                idx = int(stale.rsplit("_", 1)[1].split(".")[0])
                if idx >= snapshot["n_procs"]:
                    os.remove(stale)
                    npz = os.path.join(final_dir, offload_states_name(idx) + ".npz")
                    if os.path.isfile(npz):
                        os.remove(npz)
        _write_payloads(final_dir, files)
        _save_barrier()
        if snapshot["writer"]:
            write_manifest(final_dir, extra=snapshot["manifest_meta"])

    if save_latest and snapshot["writer"]:
        write_latest(save_dir, tag)
    return final_dir


def save_checkpoint(engine, save_dir: str, tag: Optional[str] = None, client_state: Dict = {},
                    save_latest: bool = True):
    snapshot = snapshot_checkpoint(engine, tag=tag, client_state=client_state)
    write_snapshot(snapshot, save_dir, save_latest=save_latest)
    _save_barrier()
    logger.info(f"[deepspeed_tpu] saved checkpoint {snapshot['tag']} to {save_dir}")
    return True


def _merge_elastic(ckpt_dir: str) -> Dict[str, np.ndarray]:
    """Merge per-DP-rank optim shards back into full flat arrays (any saved dp size)."""
    with open(os.path.join(ckpt_dir, "optim_shapes.json")) as f:
        manifest = json.load(f)
    saved_dp = manifest["dp_world_size"]
    shapes = manifest["shapes"]
    merged: Dict[str, List[np.ndarray]] = {k: [None] * saved_dp for k in shapes}
    for dp_rank in range(saved_dp):
        path = os.path.join(ckpt_dir, optim_states_name(dp_rank) + ".npz")
        with np.load(path) as data:
            for key in data.files:
                merged[key][dp_rank] = data[key]
    out = {}
    for key, chunks in merged.items():
        flat = np.concatenate(chunks)
        out[key] = flat.reshape(shapes[key])
    return out


def load_checkpoint(engine, load_dir: str, tag: Optional[str] = None,
                    load_optimizer_states: bool = True, load_lr_scheduler_states: bool = True):
    if tag is None:
        latest_path = os.path.join(load_dir, "latest")
        if os.path.isfile(latest_path):
            with open(latest_path) as f:
                tag = f.read().strip()
        else:
            logger.warning(f"Unable to find latest file at {latest_path}, "
                           "if trying to load latest checkpoint please pass a valid tag.")
            return None, {}
    ckpt_dir = _ckpt_dir(load_dir, tag)
    if not os.path.isdir(ckpt_dir):
        logger.warning(f"Client provided checkpoint tag {tag} does not exist in {load_dir}")
        return None, {}
    ok, reason = verify_checkpoint(ckpt_dir)
    if not ok:
        # torn / partially-written save (a crash mid-write) — refuse it rather
        # than load silently-corrupt state; auto-resume falls back to an older
        # committed tag (resilience/auto_resume.py)
        logger.warning(f"[deepspeed_tpu] REFUSING to load checkpoint {tag}: {reason}")
        return None, {}

    with open(os.path.join(ckpt_dir, model_states_name() + ".json")) as f:
        meta = json.load(f)
    if meta.get("external_master"):
        # written by the removed external-master step path: no master was saved and
        # the optimizer state is the client's own flat shard, which no engine holds
        raise ValueError(
            f"checkpoint {tag} in {load_dir} was saved by an external-master "
            "optimizer (metadata external_master: true); that step path was "
            "removed and this engine cannot hold its state")

    params = _load_tree_npz(os.path.join(ckpt_dir, model_states_name() + ".npz"),
                            engine._ckpt_export(engine.params, "params"))
    engine.params = jax.device_put(engine._ckpt_import(params, "params"),
                                   engine._param_shardings)

    engine.global_steps = meta["global_steps"]
    engine.micro_steps = meta["micro_steps"]
    engine.skipped_steps = meta["skipped_steps"]
    for g, src in zip(engine.optimizer.param_groups, meta.get("param_groups", [])):
        src = dict(src)
        if "betas" in src and isinstance(src["betas"], list):
            src["betas"] = tuple(src["betas"])
        g.update(src)
    if load_lr_scheduler_states and engine.lr_scheduler is not None and meta.get("lr_scheduler"):
        engine.lr_scheduler.load_state_dict(meta["lr_scheduler"])

    engine.scaler_state = _load_tree_npz(os.path.join(ckpt_dir, "loss_scaler.npz"), engine.scaler_state)

    if load_optimizer_states:
        offload = getattr(engine, "_offload", None)
        has_region_layout = bool(_offload_manifests(ckpt_dir))

        def offload_template():
            # leaf-shaped numpy skeleton: avoids assembling engine.master_params
            # (impossible on a multi-host offload engine, whose buffers are partial)
            return jax.tree_util.tree_unflatten(
                offload._treedef, [np.zeros(shp, np.float32) for shp in offload._shapes])

        if has_region_layout:
            if offload is not None and _scatter_offload_regions(ckpt_dir, offload):
                pass  # same topology: regions copied straight into the local buffers
            elif offload is not None:
                # topology changed: reassemble full leaves, then scatter locally
                master_flat, ea_flat, eas_flat = _load_offload_regions(ckpt_dir)
                t = offload_template()
                offload.load_trees(_unflatten_like(t, master_flat, numpy=True),
                                   _unflatten_like(t, ea_flat, numpy=True),
                                   _unflatten_like(t, eas_flat, numpy=True))
            else:
                master_flat, ea_flat, eas_flat = _load_offload_regions(ckpt_dir)
                master = _unflatten_like(
                    engine._ckpt_export(engine.master_params, "master"), master_flat)
                engine.master_params = jax.device_put(
                    engine._ckpt_import(master, "master"), engine._master_shardings)
                opt_flat = {f"exp_avg/{k}": v for k, v in ea_flat.items()}
                opt_flat.update({f"exp_avg_sq/{k}": v for k, v in eas_flat.items()})
                opt = _unflatten_like(engine._ckpt_export(engine.opt_state, "opt"), opt_flat)
                engine.opt_state = jax.device_put(
                    engine._ckpt_import(opt, "opt"), engine._opt_shardings)
        else:
            merged = _merge_elastic(ckpt_dir)
            master_flat = {k[len("master/"):]: v for k, v in merged.items() if k.startswith("master/")}
            opt_flat = {k[len("opt/"):]: v for k, v in merged.items() if k.startswith("opt/")}
            if hasattr(engine, "_onebit") and meta["dp_world_size"] != engine.dp_size:
                # OneBitAdam state sizes are dp-dependent (padded moments, per-worker
                # error buffers); adapt them instead of failing the reshape below.
                # (1-bit Adam requires replicated params, so no _ckpt_export needed.)
                opt_flat = engine._onebit.elastic_adapt(opt_flat, _flatten_with_paths(engine.opt_state))
            if offload is not None:
                # host-tier state: unflatten on the host and copy into the flat offload
                # buffers — never materialize master/moments on device
                t = offload_template()
                ea = {k[len("exp_avg/"):]: v for k, v in opt_flat.items()
                      if k.startswith("exp_avg/")}
                eas = {k[len("exp_avg_sq/"):]: v for k, v in opt_flat.items()
                       if k.startswith("exp_avg_sq/")}
                offload.load_trees(_unflatten_like(t, master_flat, numpy=True),
                                   _unflatten_like(t, ea, numpy=True),
                                   _unflatten_like(t, eas, numpy=True))
            else:
                master = _unflatten_like(
                    engine._ckpt_export(engine.master_params, "master"), master_flat)
                engine.master_params = jax.device_put(
                    engine._ckpt_import(master, "master"), engine._master_shardings)
                opt = _unflatten_like(engine._ckpt_export(engine.opt_state, "opt"), opt_flat)
                engine.opt_state = jax.device_put(
                    engine._ckpt_import(opt, "opt"), engine._opt_shardings)
    else:
        # re-derive master from loaded params (fp16-derived restore, stage2.py:1781-1836)
        if getattr(engine, "_offload", None) is not None:
            engine._offload.load_trees(master_tree=engine.params)
        else:
            engine.master_params = jax.device_put(
                jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), engine.params),
                engine._master_shardings)

    if getattr(engine, "_comm_we", None) is not None:
        # engine-held compressed-comm error feedback: restore (with elastic
        # remap on a dp change) or, for pre-resilience checkpoints that never
        # saved it, keep the zero-initialized buffers
        from ..resilience.elastic import restore_comm_ef
        restore_comm_ef(engine, ckpt_dir)

    logger.info(f"[deepspeed_tpu] loaded checkpoint {tag} from {load_dir} "
                f"(saved dp={meta['dp_world_size']}, current dp={engine.dp_size})")
    return ckpt_dir, meta.get("client_state", {})
