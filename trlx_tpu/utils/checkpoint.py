"""Crash-atomic, self-managing component checkpointing.

The reference declares checkpoint_interval and computes do_save but never
calls save() from either learn loop, and its save/load swallows exceptions
(reference: trlx/model/__init__.py:101-129, SURVEY §3.6). Here save/restore
is explicit and raises on failure, the trainers call it on the configured
interval, and — because the whole point of checkpointing is surviving
preemption — the save itself survives being preempted:

- ``save_components`` writes into a ``<dir>.tmp-<suffix>`` staging
  directory and commits with ``os.replace``. A process killed mid-save
  leaves a dead staging directory and an intact previous checkpoint; it
  can NEVER leave a half-written directory under the final name.
- ``meta.json`` (plain-python components, also the commit marker — it is
  written last) goes through its own write-temp-then-``os.replace``.
- Step checkpoints (``save_step_checkpoint``) live under a run directory
  as ``step_<N>/`` with an atomically-updated ``LATEST`` marker;
  ``find_latest_checkpoint`` resolves the newest VALID one (skipping
  staging leftovers and dirs missing the commit marker), which is what
  ``train.resume_from: auto`` resumes from. ``train.keep_checkpoints``
  bounds disk: older committed step dirs (and dead staging dirs) are
  garbage-collected after each successful save.
- ``restore_components`` accepts either a checkpoint dir or a run dir
  (falling back to the newest valid step inside), and raises ONE
  actionable error — expected components vs. what is actually on disk —
  instead of a bare per-component FileNotFoundError.
- **End-to-end byte integrity** (docs "Fault tolerance", fleet
  containment). Crash-atomicity protects against TORN writes; it says
  nothing about bit-rot, a truncated object-store download, or a torn
  meta.json forged by a buggy tool — all of which previously restored
  garbage weights silently into the trainer, the serve hot-swap, and a
  fleet-wide rollout (the reload smoke probe only catches non-finite
  logits, not wrong-but-finite ones). ``save_components`` now embeds a
  per-file SHA-256 manifest in meta.json (still the last-written commit
  marker, so the manifest commits atomically with the checkpoint);
  every restore path calls :func:`verify_checkpoint` first and raises
  the typed :class:`CheckpointCorrupt` on any mismatch. A corrupt step
  directory is **quarantined** — renamed ``step_<N>.corrupt-<suffix>``
  (``checkpoint/quarantined``), which makes it invisible to
  ``find_latest_checkpoint`` — so trainer auto-resume, engine boot, and
  ``/admin/reload`` all degrade to the previous good step instead of
  installing garbage. Pre-manifest checkpoints restore as before
  (``checkpoint/verify_skipped``).
- The commit renames themselves are durable: after every
  ``os.replace`` the parent directory is fsynced — without it a power
  loss can forget the rename even though the file contents were synced
  (the renamed entry lives in the DIRECTORY's blocks).

Only JAX process 0 writes (single-writer; params are replicated or
re-shardable on restore) — gated HERE, not at call sites, so every save
path inherits it. Components are a flat dict {name: pytree | scalar-dict};
arrays go through Orbax, plain-python metadata through JSON.
"""

import hashlib
import itertools
import json
import os
import re
import shutil
from typing import Any, Dict, Optional

import jax
import numpy as np

#: commit marker: always written, and written LAST — a directory without
#: it is a torn write, not a checkpoint
META_NAME = "meta.json"
LATEST_NAME = "LATEST"
_STEP_RE = re.compile(r"^step_(\d+)$")
#: reserved meta.json key carrying the per-file integrity manifest —
#: never a component name (double underscores keep it out of any
#: trainer's get_components() namespace)
MANIFEST_KEY = "__manifest__"


class CheckpointCorrupt(RuntimeError):
    """Checkpoint bytes failed end-to-end verification against the
    manifest in its commit marker (bit-rot, truncation, a torn
    meta.json). The directory has been quarantined when possible; run
    dirs fall back to the previous good step, explicit checkpoint paths
    surface this error."""


def _is_array_tree(obj: Any) -> bool:
    leaves = jax.tree_util.tree_leaves(obj)
    return bool(leaves) and all(
        hasattr(x, "shape") or isinstance(x, (np.ndarray, float, int)) for x in leaves
    )


def _is_empty(x: Any) -> bool:
    return getattr(x, "size", 1) == 0


def _empty_leaves_out(tree: Any) -> Any:
    """``tree`` with every zero-size array leaf — e.g. the hydra's
    ``frozen_base.blocks`` when every layer is unfrozen (ILQL's shipped
    ``num_layers_unfrozen: -1``: zero frozen layers) — swapped for a
    0-d int8 placeholder. Orbax refuses zero-size arrays outright
    ("Cannot save arrays with zero size"), and they hold no bytes to
    save: the placeholder keeps the tree structure on disk, and
    :func:`_empty_leaves_back` rebuilds the real leaves from the restore
    template's shapes."""
    return jax.tree_util.tree_map(
        lambda x: np.zeros((), np.int8) if _is_empty(x) else x, tree
    )


def _empty_leaves_back(template: Any, restored: Any, shardings: Any = None):
    """Inverse of :func:`_empty_leaves_out` after a restore: wherever
    ``template`` has a zero-size leaf, a zero-size array of its shape and
    dtype (placed under the matching ``shardings`` entry when given)
    replaces the placeholder orbax read back."""

    def back(t, r, sh=None):
        if not _is_empty(t):
            return r
        empty = np.zeros(t.shape, t.dtype)
        return jax.device_put(empty, sh) if sh is not None else empty

    if shardings is None:
        return jax.tree_util.tree_map(back, template, restored)
    return jax.tree_util.tree_map(back, template, restored, shardings)


def _main_process() -> bool:
    from trlx_tpu.parallel import is_main_process

    return is_main_process()


def _fsync_dir(path: str) -> None:
    """fsync a DIRECTORY so a just-committed ``os.replace`` rename
    survives power loss — fsyncing the file pins its contents, but the
    rename lives in the parent directory's blocks. Best-effort on
    filesystems/platforms that refuse O_RDONLY directory handles (the
    rename is still crash-atomic there, just not power-loss-durable)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return  # e.g. Windows: directories are not openable for fsync
    try:
        os.fsync(fd)
    except OSError:
        return  # e.g. fsync unsupported on this mount; stay best-effort
    finally:
        os.close(fd)


def _atomic_write_text(text: str, path: str) -> None:
    """write-temp-then-rename: readers see the old content or the new,
    never a torn write (a preemption mid-``json.dump`` previously left a
    truncated meta.json under the final name). The parent directory is
    fsynced after the rename so the COMMIT survives power loss too."""
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(os.path.dirname(path) or ".")


def is_valid_checkpoint(directory: str) -> bool:
    """Committed checkpoint dir: exists, is not a staging/aside/
    quarantine leftover, and carries the commit marker (meta.json,
    written last)."""
    base = os.path.basename(os.path.normpath(directory))
    if ".tmp-" in base or ".old-" in base or ".corrupt-" in base:
        return False
    return os.path.isdir(directory) and os.path.exists(
        os.path.join(directory, META_NAME)
    )


def _file_sha256(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            digest.update(chunk)
    return digest.hexdigest()


def build_manifest(directory: str) -> Dict[str, Dict[str, Any]]:
    """Per-file integrity manifest of everything under ``directory``:
    ``{relpath: {"sha256": hex, "bytes": size}}``, excluding meta.json
    itself (it CARRIES the manifest). Paths use '/' separators so a
    checkpoint verifies across platforms."""
    directory = os.path.abspath(directory)
    manifest: Dict[str, Dict[str, Any]] = {}
    for root, _, files in os.walk(directory):
        for fname in sorted(files):
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, directory).replace(os.sep, "/")
            if rel == META_NAME:
                continue
            manifest[rel] = {
                "sha256": _file_sha256(path),
                "bytes": os.path.getsize(path),
            }
    return manifest


def verify_checkpoint(directory: str, component: Optional[str] = None) -> bool:
    """Verify ``directory``'s bytes against the manifest in its commit
    marker. Returns True when verified, False when the checkpoint
    predates manifests (nothing to verify against —
    ``checkpoint/verify_skipped``). Raises :class:`CheckpointCorrupt`
    naming the first damaged file on any mismatch, and for a torn or
    unreadable meta.json (the marker itself is damage). ``component``
    limits verification to one component's files (the serve-side
    partial restore reads only ``params/``)."""
    from trlx_tpu import telemetry
    from trlx_tpu.supervisor import chaos

    directory = os.path.abspath(directory)

    def corrupt(detail: str) -> CheckpointCorrupt:
        telemetry.inc("checkpoint/verify_failures")
        return CheckpointCorrupt(
            f"checkpoint '{directory}' failed integrity verification: "
            f"{detail}. The bytes on disk are not the bytes that were "
            f"saved — do not install them; quarantine and fall back to "
            f"the previous step (docs 'Fault tolerance', quarantine "
            f"runbook)."
        )

    try:
        # the drill seam: an injected exc IS a verification failure,
        # driving quarantine/fallback exactly like real bit-rot
        chaos.maybe_inject("checkpoint_verify")
    except chaos.ChaosError as e:
        raise corrupt(f"chaos-injected ({e})") from e
    meta_path = os.path.join(directory, META_NAME)
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        raise corrupt(
            f"torn/unreadable '{META_NAME}' ({type(e).__name__}: {e}) — "
            f"the commit marker itself is damaged"
        ) from e
    manifest = meta.get(MANIFEST_KEY) if isinstance(meta, dict) else None
    if manifest is None:
        telemetry.inc("checkpoint/verify_skipped")
        return False
    files = dict(manifest.get("files") or {})
    if component is not None:
        prefix = component.rstrip("/") + "/"
        files = {rel: e for rel, e in files.items() if rel.startswith(prefix)}
    for rel in sorted(files):
        entry = files[rel]
        path = os.path.join(directory, *rel.split("/"))
        try:
            size = os.path.getsize(path)
        except OSError:
            raise corrupt(f"'{rel}' is missing from disk") from None
        if int(entry.get("bytes", size)) != size:
            raise corrupt(
                f"'{rel}' is truncated: manifest says "
                f"{entry['bytes']} bytes, disk has {size}"
            )
        digest = _file_sha256(path)
        if digest != entry.get("sha256"):
            raise corrupt(
                f"'{rel}' content hash mismatch (sha256 {digest} != "
                f"manifest {entry.get('sha256')}) — bit-rot or an "
                f"out-of-band overwrite"
            )
    telemetry.inc("checkpoint/verified")
    return True


#: collision counter for quarantine renames within one process — paired
#: with the pid (not wall time: library timing goes through the
#: supervisor clock, and a quarantine name only needs uniqueness)
_quarantine_seq = itertools.count(1)


def quarantine_checkpoint(directory: str, reason: str = "") -> Optional[str]:
    """Rename a corrupt checkpoint aside as ``<dir>.corrupt-<suffix>``
    so ``find_latest_checkpoint`` stops resolving it and the evidence
    survives for the operator (quarantined dirs are never GC'd).
    Returns the quarantine path, or None when the rename was impossible
    (already gone, or a sibling process won the race)."""
    from trlx_tpu import telemetry

    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return None
    aside = f"{directory}.corrupt-{os.getpid()}"
    while os.path.exists(aside):
        aside = f"{directory}.corrupt-{os.getpid()}-{next(_quarantine_seq)}"
    try:
        os.replace(directory, aside)
    except OSError:
        return None  # concurrent quarantine/GC won; nothing left to move
    _fsync_dir(os.path.dirname(aside) or ".")
    telemetry.inc("checkpoint/quarantined")
    print(
        f"[trlx_tpu] QUARANTINED corrupt checkpoint '{directory}' -> "
        f"'{aside}'" + (f" ({reason})" if reason else ""),
        flush=True,
    )
    return aside


def verify_or_quarantine(directory: str,
                         component: Optional[str] = None) -> bool:
    """:func:`verify_checkpoint`, quarantining the directory on failure
    before re-raising — the restore paths' one-call integrity gate."""
    try:
        return verify_checkpoint(directory, component=component)
    except CheckpointCorrupt as e:
        aside = quarantine_checkpoint(directory, reason=str(e))
        if aside is not None:
            raise CheckpointCorrupt(
                f"{e} [quarantined to '{aside}']"
            ) from e
        raise


def save_components(components: Dict[str, Any], directory: str) -> None:
    """Write all components under ``directory``, crash-atomically.

    Everything lands in a ``<directory>.tmp-<pid>`` staging dir first
    (arrays via Orbax, then meta.json as the commit marker); the final
    name appears only via ``os.replace``. Replacing an existing
    checkpoint renames it aside first, so a crash at any instant leaves
    either the old committed dir or the new one reachable — never a
    partial mix. No-op off JAX process 0 (single-writer)."""
    if not _main_process():
        return
    import orbax.checkpoint as ocp

    from trlx_tpu import telemetry

    with telemetry.span("checkpoint_save"):
        directory = os.path.abspath(directory)
        parent = os.path.dirname(directory)
        if parent:
            os.makedirs(parent, exist_ok=True)
        staging = f"{directory}.tmp-{os.getpid()}"
        if os.path.isdir(staging):
            shutil.rmtree(staging)  # leftover from a previous crashed save
        os.makedirs(staging)
        meta = {}
        with ocp.PyTreeCheckpointer() as ckptr:
            for name, obj in components.items():
                if _is_array_tree(obj):
                    ckptr.save(
                        os.path.join(staging, name), _empty_leaves_out(obj),
                        force=True,
                    )
                else:
                    meta[name] = obj
        # integrity manifest over everything the writers produced (built
        # AFTER the checkpointers close, so async flushes are on disk),
        # then the commit marker: written last, atomically, inside
        # staging — manifest and checkpoint commit as one unit
        meta[MANIFEST_KEY] = {
            "algo": "sha256", "files": build_manifest(staging),
        }
        _atomic_write_text(json.dumps(meta), os.path.join(staging, META_NAME))

        if os.path.isdir(directory):
            # rename-aside then promote: os.replace cannot replace a
            # non-empty dir, and deleting the old checkpoint BEFORE the new
            # one is committed would reopen the exact corruption window this
            # module exists to close
            aside = f"{directory}.old-{os.getpid()}"
            if os.path.isdir(aside):
                shutil.rmtree(aside)
            os.replace(directory, aside)
            os.replace(staging, directory)
            shutil.rmtree(aside)
        else:
            os.replace(staging, directory)
        # the promote rename lives in the parent directory's blocks;
        # without this fsync a power loss can undo the commit
        _fsync_dir(parent or ".")
        telemetry.inc("checkpoint/saves")


def step_dir(run_dir: str, step: int) -> str:
    return os.path.join(os.path.abspath(run_dir), f"step_{int(step)}")


def find_latest_checkpoint(run_dir: str) -> Optional[str]:
    """Newest VALID ``step_<N>`` checkpoint under ``run_dir``, or None.

    Prefers the atomically-written LATEST marker when it points at a
    valid dir; otherwise scans — half-written dirs (dead staging, torn
    writes missing the commit marker) are skipped, so a save killed
    mid-write falls back to the previous committed step."""
    run_dir = os.path.abspath(run_dir)
    if not os.path.isdir(run_dir):
        return None
    latest_path = os.path.join(run_dir, LATEST_NAME)
    if os.path.exists(latest_path):
        with open(latest_path) as f:
            named = os.path.join(run_dir, f.read().strip())
        if is_valid_checkpoint(named):
            return named
    best = None
    best_step = -1
    for entry in os.listdir(run_dir):
        m = _STEP_RE.match(entry)
        if not m:
            continue
        path = os.path.join(run_dir, entry)
        if int(m.group(1)) > best_step and is_valid_checkpoint(path):
            best, best_step = path, int(m.group(1))
    return best


def gc_checkpoints(run_dir: str, keep: int) -> None:
    """Retention: delete all but the newest ``keep`` committed step dirs
    (``keep <= 0`` keeps everything), plus any dead staging/aside
    leftovers from crashed saves. Invalid step dirs are removed too —
    they are torn writes, not restorable state."""
    from trlx_tpu import telemetry

    run_dir = os.path.abspath(run_dir)
    if not os.path.isdir(run_dir):
        return
    steps = []
    for entry in os.listdir(run_dir):
        path = os.path.join(run_dir, entry)
        if ".tmp-" in entry or ".old-" in entry:
            shutil.rmtree(path, ignore_errors=True)
            telemetry.inc("fault/checkpoint_debris_cleared")
            continue
        m = _STEP_RE.match(entry)
        if not m:
            continue
        if not is_valid_checkpoint(path):
            shutil.rmtree(path, ignore_errors=True)
            telemetry.inc("fault/checkpoint_debris_cleared")
            continue
        steps.append((int(m.group(1)), path))
    if keep and keep > 0:
        for _, path in sorted(steps)[:-keep]:
            shutil.rmtree(path, ignore_errors=True)


def save_step_checkpoint(
    components: Dict[str, Any], run_dir: str, step: int, keep: int = 0
) -> str:
    """One training-step checkpoint under ``run_dir/step_<step>``:
    atomic component save, LATEST marker update (also atomic), then
    retention GC. Returns the checkpoint path. No-op (path still
    returned) off JAX process 0."""
    path = step_dir(run_dir, step)
    if not _main_process():
        return path
    save_components(components, path)
    _atomic_write_text(
        os.path.basename(path), os.path.join(os.path.dirname(path), LATEST_NAME)
    )
    gc_checkpoints(run_dir, keep)
    return path


def _resolve_restore_dir(directory: str) -> Optional[str]:
    """A directory the user can point restore at: a checkpoint itself, or
    a run dir whose newest valid step checkpoint is used."""
    if is_valid_checkpoint(directory):
        return directory
    return find_latest_checkpoint(directory)


def _resolve_verified_dir(directory: str, expected,
                          component: Optional[str] = None) -> str:
    """Resolve-and-verify loop shared by the restore paths: resolve
    ``directory`` (checkpoint or run dir), byte-verify the candidate,
    and on corruption quarantine it and — when ``directory`` is a run
    dir — resolve again, walking back to the previous good step. A
    corrupt checkpoint pointed at DIRECTLY re-raises: there is nothing
    behind it to fall back to."""
    previous = None
    while True:
        pointed_directly = is_valid_checkpoint(directory)
        resolved = directory if pointed_directly \
            else find_latest_checkpoint(directory)
        if resolved is None:
            if os.path.isdir(directory):
                contents = sorted(os.listdir(directory)) or ["<empty>"]
                detail = (
                    f"exists but holds no committed checkpoint: {contents}"
                )
            else:
                detail = "does not exist"
            raise FileNotFoundError(
                f"no checkpoint at '{directory}' ({detail}). Expected "
                f"either a checkpoint directory with components "
                f"{expected} + '{META_NAME}', or a run directory "
                f"containing committed 'step_<N>' checkpoints. A save "
                f"killed mid-write leaves only a '*.tmp-*' staging dir "
                f"and a corrupt one is quarantined as '*.corrupt-*' — "
                f"neither is restorable; point resume_from at the run "
                f"directory (or 'auto') to fall back to the newest "
                f"committed step."
            )
        try:
            verify_or_quarantine(resolved, component=component)
            return resolved
        except CheckpointCorrupt:
            if pointed_directly or resolved == previous:
                # nothing behind it to fall back to — or the quarantine
                # rename failed and resolution is stuck on the same dir
                raise
            previous = resolved
            print(
                f"[trlx_tpu] falling back past corrupt checkpoint "
                f"'{resolved}' to the previous good step under "
                f"'{directory}'",
                flush=True,
            )


def restore_components(template: Dict[str, Any], directory: str) -> Dict[str, Any]:
    """Restore into the structure of `template` (same component names/shapes).

    `directory` may be a single checkpoint or a run dir of ``step_<N>``
    checkpoints (the newest valid one is used — half-written ones are
    skipped). Every candidate is byte-verified against its manifest
    first: a corrupt step is quarantined and, when ``directory`` is a
    run dir, the previous good step is tried instead (auto-resume
    degrades to last-known-good); pointing at a corrupt checkpoint
    DIRECTLY raises :class:`CheckpointCorrupt`. Missing
    paths/components raise ONE error naming what was expected and what
    is actually on disk, instead of a bare per-component
    FileNotFoundError."""
    import orbax.checkpoint as ocp

    directory = os.path.abspath(directory)
    directory = _resolve_verified_dir(directory, sorted(template))
    out = {}
    meta_path = os.path.join(directory, META_NAME)
    meta = {}
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    missing = [
        name
        for name in template
        if not os.path.isdir(os.path.join(directory, name)) and name not in meta
    ]
    if missing:
        raise FileNotFoundError(
            f"checkpoint '{directory}' is missing components {missing}: "
            f"expected {sorted(template)}, found on disk "
            f"{sorted(os.listdir(directory))} with meta keys "
            f"{sorted(meta)}. The checkpoint was probably written by a "
            f"different trainer/method — components must match the "
            f"restoring trainer's get_components()."
        )
    with ocp.PyTreeCheckpointer() as ckptr:
        for name, obj in template.items():
            path = os.path.join(directory, name)
            if os.path.isdir(path):
                # restore WITH the template's shardings: arrays land
                # directly on the current mesh (and reshard correctly when
                # restoring onto a different topology than the save ran on)
                item = _empty_leaves_out(obj)
                restore_args = ocp.checkpoint_utils.construct_restore_args(
                    item
                )
                out[name] = _empty_leaves_back(obj, ckptr.restore(
                    path, item=item, restore_args=restore_args
                ))
            else:
                out[name] = meta[name]
    from trlx_tpu import telemetry

    telemetry.inc("checkpoint/restores")
    return out


def restore_component_sharded(
    name: str, template: Any, shardings: Any, directory: str
) -> Any:
    """Partial, streaming restore of ONE array component.

    ``template`` is a ShapeDtypeStruct pytree covering a SUBSET of the
    stored tree (e.g. the serve-side decode views without the reference
    branch / value head); subtrees absent from it are never read off
    disk. Each leaf restores straight into a device buffer under its
    entry in ``shardings`` (a matching NamedSharding pytree), so host
    staging is Orbax's per-leaf pipeline — peak ~one leaf, never the
    whole tree — and a tp/fsdp-sharded engine reads only its shards of
    each leaf. ``directory`` resolves like :func:`restore_components`
    (checkpoint dir or run dir), byte-verifying ONLY this component's
    manifest entries — a corrupt step is quarantined and a run dir
    falls back to the previous good one."""
    import orbax.checkpoint as ocp

    directory = os.path.abspath(directory)
    resolved = _resolve_verified_dir(directory, [name], component=name)
    path = os.path.join(resolved, name)
    if not os.path.isdir(path):
        raise FileNotFoundError(
            f"checkpoint '{resolved}' has no array component '{name}' "
            f"(found on disk: {sorted(os.listdir(resolved))})"
        )
    restore_args = jax.tree_util.tree_map(
        lambda sds, sh: ocp.RestoreArgs() if _is_empty(sds)
        else ocp.ArrayRestoreArgs(
            sharding=sh, dtype=getattr(sds, "dtype", None)
        ),
        template, shardings,
    )
    with ocp.PyTreeCheckpointer() as ckptr:
        # transforms={} switches Orbax to lazy per-key matching, which is
        # what makes the ITEM-IS-A-SUBSET restore legal (without it the
        # tree structures must match exactly)
        out = ckptr.restore(
            path, item=_empty_leaves_out(template),
            restore_args=restore_args, transforms={},
        )
    out = _empty_leaves_back(template, out, shardings)
    from trlx_tpu import telemetry

    telemetry.inc("checkpoint/restores")
    return out
