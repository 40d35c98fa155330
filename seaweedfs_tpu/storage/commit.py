"""Two-phase staged-file commit shared by EC encode, vacuum, and tier moves.

Every multi-file transition in the storage layer has the same shape: new
files are produced next to live ones, then a rename swap retires the old
state. A crash mid-swap used to leave a volume that is neither fully old
nor fully new (a partial EC shard set, a compacted .dat with the stale
.idx). This module makes the transition all-or-nothing, the way f4 treats
encode-and-retire as an atomic recoverable state change:

1. **stage** — every output is written to a sibling staging name
   (``<final>.tmp``; vacuum keeps its reference ``.cpd``/``.cpx`` names);
2. **harden** — each staged file is fsync'd (the stage span the commit
   runs in counts them, and those of ``SLOW_FSYNC_S`` or more);
3. **commit point** — a manifest (``<base>.commit``, JSON: staged files +
   their exact sizes + post-rename deletions) is written atomically
   (tmp + rename) and the directory is fsync'd;
4. **apply** — each staged file is renamed onto its final name;
5. **cleanup** — the manifest is unlinked, directory fsync'd again.

Crash before 3: the restart scan finds staged files with no manifest and
garbage-collects them — the OLD state is intact (rollback). Crash at or
after 3: the manifest exists, every staged file is known durable, and the
scan re-executes 4-5 (roll-forward); ``os.replace`` is idempotent, so a
half-applied rename pass completes cleanly. There is no reachable state
where the swap is half-applied after recovery runs.

:func:`recover_directory` is that restart scan; DiskLocation runs it
before loading any volume. Fault points named ``<tag>.staged`` /
``<tag>.manifest`` / ``<tag>.rename`` / ``<tag>.renamed`` fire at each
protocol step so the crash matrix can kill the process between every pair
of steps (util/faultpoints.py).
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

from ..stats import trace
from ..util import faultpoints, glog

COMMIT_EXT = ".commit"
STAGING_SUFFIX = ".tmp"

# staging names recovery may garbage-collect when no manifest claims them:
# generic ``.tmp`` plus vacuum's reference-parity ``.cpd``/``.cpx`` pair
_ORPHAN_EXTS = (STAGING_SUFFIX, ".cpd", ".cpx")


# a staged file's fsync this long or longer is counted as slow by the stage
# a commit runs in (``slow_fsyncs``): a stalled disk, not a dear one. Not a
# knob: the rows of two machines are compared by it
SLOW_FSYNC_S = 0.2


def fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def fsync_dir(path: str) -> None:
    """Make renames/unlinks in ``path`` durable. Some filesystems refuse
    O_RDONLY fsync on directories; a refusal degrades to the pre-commit
    behavior rather than failing the operation."""
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


def atomic_write(path: str, data: bytes, mode: Optional[int] = None) -> None:
    """Single-file atomic durable write: tmp → fsync → rename → dir fsync.
    Readers see the old contents or the new, never a torn prefix."""
    tmp = path + STAGING_SUFFIX
    flags = os.O_WRONLY | os.O_CREAT | os.O_TRUNC
    fd = os.open(tmp, flags, mode if mode is not None else 0o666)
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    os.replace(tmp, path)
    fsync_dir(os.path.dirname(os.path.abspath(path)))


class StagedCommit:
    """One two-phase transition for one volume.

    ``base_path`` is the volume base (``<dir>/<collection>_<vid>``); the
    manifest lives at ``<base>.commit``. ``tag`` names the operation
    (``ec.encode``, ``vacuum``, ``tier.download``) and prefixes the fault
    points fired inside :meth:`commit`.
    """

    def __init__(self, base_path: str, tag: str):
        self.base_path = os.path.abspath(base_path)
        self.dir = os.path.dirname(self.base_path)
        self.manifest_path = self.base_path + COMMIT_EXT
        self.tag = tag
        self._files: dict[str, str] = {}  # final abs path -> staged abs path
        self._remove: list[str] = []

    def stage(self, final_path: str, tmp_path: Optional[str] = None) -> str:
        """Register an output; returns the staging path the caller must
        write. Default staging name is ``<final>.tmp``."""
        final_path = os.path.abspath(final_path)
        tmp_path = os.path.abspath(tmp_path or final_path + STAGING_SUFFIX)
        self._files[final_path] = tmp_path
        return tmp_path

    def remove_on_commit(self, path: str) -> None:
        """Unlink ``path`` after the rename pass (e.g. the ``.tier``
        descriptor once the downloaded ``.dat`` is back in place). Recorded
        in the manifest so roll-forward repeats it."""
        self._remove.append(os.path.abspath(path))

    def commit(self) -> None:
        """Steps 2-5. After this returns, the new state is durable; if the
        process dies inside, recover_directory finishes or undoes it."""
        first_staged = next(iter(self._files.values()), None)
        faultpoints.fire(self.tag + ".staged", path=first_staged)
        entries = {}
        for final, tmp in self._files.items():
            # the stage span this commit runs in, if any, counts its fsyncs
            # and the slow ones among them
            t = time.perf_counter()
            fsync_file(tmp)
            slow = time.perf_counter() - t >= SLOW_FSYNC_S
            trace.add_stage_count("fsyncs")
            trace.add_stage_count("slow_fsyncs", int(slow))
            entries[os.path.basename(final)] = {
                "tmp": os.path.basename(tmp),
                "size": os.path.getsize(tmp),
            }
        manifest = {
            "tag": self.tag,
            "files": entries,
            "remove": [os.path.basename(p) for p in self._remove],
        }
        atomic_write(
            self.manifest_path, json.dumps(manifest, indent=1).encode()
        )
        # -- the commit point: the manifest is durable -----------------------
        faultpoints.fire(self.tag + ".manifest", path=self.manifest_path)
        _apply_manifest(self.manifest_path, manifest, fault_tag=self.tag)

    def abort(self) -> None:
        """Drop staged files (in-process failure before/inside commit)."""
        for tmp in self._files.values():
            try:
                os.unlink(tmp)
            except OSError:
                pass
        for p in (self.manifest_path + STAGING_SUFFIX, self.manifest_path):
            try:
                os.unlink(p)
            except OSError:
                pass


def _apply_manifest(manifest_path: str, manifest: dict,
                    fault_tag: Optional[str] = None) -> None:
    """Steps 4-5, shared by the live commit and restart roll-forward.
    Renames are applied in sorted final-name order so a crash mid-pass is
    reproducible for the crash matrix."""
    directory = os.path.dirname(os.path.abspath(manifest_path))
    files = manifest.get("files", {})
    first = True
    for final_name in sorted(files):
        tmp = os.path.join(directory, files[final_name]["tmp"])
        final = os.path.join(directory, final_name)
        if os.path.exists(tmp):
            os.replace(tmp, final)
        if first and fault_tag:
            faultpoints.fire(fault_tag + ".rename")
            first = False
    fsync_dir(directory)
    if fault_tag:
        faultpoints.fire(fault_tag + ".renamed")
    for name in manifest.get("remove", []):
        try:
            os.unlink(os.path.join(directory, name))
        except FileNotFoundError:
            pass
    os.unlink(manifest_path)
    fsync_dir(directory)


def _manifest_complete(manifest_path: str, manifest: dict) -> bool:
    """Roll-forward precondition: every listed output exists — staged at
    its recorded size, or already renamed into place. fsync-before-manifest
    ordering makes this always true after a genuine crash; a False answer
    means the manifest is lying (torn by filesystem loss or hand-edited)
    and rolling forward would install short files."""
    directory = os.path.dirname(os.path.abspath(manifest_path))
    for final_name, ent in manifest.get("files", {}).items():
        tmp = os.path.join(directory, ent["tmp"])
        final = os.path.join(directory, final_name)
        want = ent.get("size", -1)
        if os.path.exists(tmp) and os.path.getsize(tmp) == want:
            continue
        if os.path.exists(final) and os.path.getsize(final) == want:
            continue
        return False
    return True


def recover_directory(directory: str) -> dict:
    """Startup recovery scan (step 0 of every DiskLocation load).

    - each ``*.commit`` manifest: roll the transition forward when every
      staged output is complete, otherwise garbage-collect its staged
      files and the manifest (the old state is still live);
    - any remaining orphan staging file (``.tmp``/``.cpd``/``.cpx``) is
      from a transition that died before its commit point: deleted.

    Returns ``{"rolled_forward": [...], "rolled_back": [...], "gc": [...]}``
    naming what was done (tests assert on it; callers log it). Idempotent —
    a crash during recovery itself re-runs cleanly.
    """
    actions: dict = {"rolled_forward": [], "rolled_back": [], "gc": []}
    if not os.path.isdir(directory):
        return actions
    entries = sorted(os.listdir(directory))
    for entry in entries:
        if not entry.endswith(COMMIT_EXT):
            continue
        manifest_path = os.path.join(directory, entry)
        try:
            with open(manifest_path) as f:
                manifest = json.load(f)
            files = manifest["files"]
            assert isinstance(files, dict)
        except Exception:
            # torn/garbage manifest: it never became a commit point
            _rollback(manifest_path, {}, actions)
            continue
        tag = manifest.get("tag", "?")
        if _manifest_complete(manifest_path, manifest):
            _apply_manifest(manifest_path, manifest)
            actions["rolled_forward"].append(f"{tag}:{entry}")
        else:
            glog.error(
                "commit manifest %s incomplete on disk; rolling back", entry
            )
            _rollback(manifest_path, manifest, actions)
            actions["rolled_back"].append(f"{tag}:{entry}")
    # orphan staging files: no manifest claimed them, so their transition
    # never committed — the live state never referenced them
    for entry in sorted(os.listdir(directory)):
        if entry.endswith(_ORPHAN_EXTS):
            path = os.path.join(directory, entry)
            try:
                os.unlink(path)
                actions["gc"].append(entry)
            except OSError:
                pass
    if actions["gc"] or actions["rolled_forward"] or actions["rolled_back"]:
        fsync_dir(directory)
    return actions


def _rollback(manifest_path: str, manifest: dict, actions: dict) -> None:
    directory = os.path.dirname(os.path.abspath(manifest_path))
    for ent in manifest.get("files", {}).values():
        tmp = os.path.join(directory, ent.get("tmp", ""))
        try:
            os.unlink(tmp)
            actions["gc"].append(os.path.basename(tmp))
        except OSError:
            pass
    try:
        os.unlink(manifest_path)
    except OSError:
        pass


def pending_commit(base_path: str) -> bool:
    """True while ``base_path`` has an unresolved commit manifest — the
    volume must not be (re)mounted until recovery resolves it."""
    return os.path.exists(base_path + COMMIT_EXT)
