"""File-level EC encode/rebuild: .dat → .ec00‥.ec13 (+ .ecx/.ecj/.vif).

Semantics mirror `weed/storage/erasure_coding/ec_encoder.go`:

- the volume's .dat is striped row-major into k data shards: rows of k×1GB
  "large blocks" while more than one full large row remains, then rows of
  k×1MB "small blocks" (zero-padded past EOF) for the tail
  (encodeDatFile, ec_encoder.go:194-231);
- shard i's bytes for a row are dat[row_start + i*block : +block];
- parity shards are the GF(2^8) matmul of the k data blocks;
- every shard file is therefore n_large×large + n_small_rows×small bytes.

Unlike the reference's fixed 256KB buffers, IO is batched in large
column-chunks sized for the backend (the TPU path feeds whole chunks to one
kernel launch). Output bytes are identical — the striping layout is a pure
function of the .dat contents.
"""

from __future__ import annotations

import collections
import functools
import hashlib
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import wait as wait_all
from typing import Optional

import numpy as np

from ..stats import trace
from ..storage import idx as idx_mod
from ..storage.types import OFFSET_SIZE, TOMBSTONE_FILE_SIZE
from ..util import faultpoints, glog
from .codec import Codec, get_codec
from .constants import (
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    Geometry,
    shard_ext,
)


def _is_hole(fd: int, start: int, length: int) -> bool:
    """True if [start, start+length) is entirely a filesystem hole.

    SEEK_DATA turns sparse sealed volumes (preallocated space, punched
    deletes) from gigabytes of kernel zero-fill reads into a single lseek;
    filesystems without the op just report everything as data."""
    import errno

    # preserve the fd offset: callers may be buffered file objects whose
    # tell() bookkeeping is built on the raw fd position
    cur = os.lseek(fd, 0, os.SEEK_CUR)
    try:
        data_off = os.lseek(fd, start, os.SEEK_DATA)
    except OSError as e:
        return e.errno == errno.ENXIO  # no data at/after start == all hole
    except (AttributeError, ValueError):
        return False
    finally:
        os.lseek(fd, cur, os.SEEK_SET)
    return data_off >= start + length


# most buffers one scatter read takes (Linux: 1024)
_IOV_MAX = os.sysconf("SC_IOV_MAX")


def _pread_into(fd: int, offset: int, views: list) -> None:
    """Fill ``views`` — writable 1-D uint8 arrays, in file order — with the
    consecutive bytes of ``fd`` from ``offset``: scatter reads
    (``os.preadv``) of at most ``_IOV_MAX`` buffers, straight into place
    and without moving the descriptor's position. A short count is not an
    error: the read goes on from where it stopped. Whatever lies past EOF
    is zeroed, because the buffers are recycled and hold an older chunk."""
    views = list(views)
    i = 0
    while i < len(views):
        got = os.preadv(fd, views[i : i + _IOV_MAX], offset)
        if got == 0:  # EOF
            break
        offset += got
        while i < len(views) and got >= len(views[i]):
            got -= len(views[i])
            i += 1
        if got:
            views[i] = views[i][got:]
    for v in views[i:]:
        v[:] = 0


def _side_by_side(workers, fn, jobs: list, width: int) -> list:
    """``fn(job)`` for every one of ``jobs``, at most ``width`` of them at
    a time, and the results in the jobs' order. This thread takes jobs
    beside up to ``width - 1`` threads of ``workers()``, a pool the process
    keeps (asked for only when a job hops), each taking the next job not
    yet begun: a job alone hops to no thread. Returns when every job begun
    has ended, also when one raised — nothing is in flight; then no more
    are begun and the call raises that error."""
    todo = collections.deque(enumerate(jobs))
    done = [None] * len(todo)

    def take():
        while True:
            try:
                i, job = todo.popleft()
            except IndexError:  # none left: the others took them
                return
            try:
                done[i] = fn(job)
            except BaseException:
                todo.clear()
                raise

    helpers = [workers().submit(take)
               for _ in range(min(width, len(todo)) - 1)]
    try:
        take()
    finally:
        wait_all(helpers)
    for helper in helpers:
        helper.result()
    return done


# Reads of one chunk the reader puts side by side: its only parameters.
# One thread copies a chunk out of the page cache at 2.0-2.6 GB/s on a v5e
# host (13 cores, gVisor over 9p) and threads scale: a rebuild's ten rows
# of 12.75 MiB take 51 ms on one thread, 26 / 22 / 16 / 12 / 12 on
# 2 / 3 / 4 / 6 / 10 alone and 53 -> 35 / 23 / 25 / 20 / 18 beside the
# next chunk's staging and the last one's copy back; a seal's 130 MiB run
# 67 -> 34 / 18 in 2 / 4 pieces alone, 69 -> 53 / 32-39 beside. Inside the
# pipeline (every width through `write_ec_files` / `rebuild_ec_files`, the
# column that decides) six is where it stops paying: a one-shard rebuild's
# pipeline 0.550 s at 1, 0.483 / 0.420 / 0.395 / 0.400 / 0.358 at
# 2 / 3 / 4 / 6 / 10 (0.280 -> 0.193 / 0.197 at 6 / 10 with six rows,
# 0.556 -> 0.353 / 0.352 with twelve), a seal's 0.849 -> 0.663 / 0.655 /
# 0.617 / 0.581 / 0.605 (0.776 -> 0.510 / 0.603 and 0.756 -> 0.534 / 0.561
# at 12+4 and 12+2+2): at ten the seal's readers, its fourteen digests and
# the copy back's four threads are more than the host's cores and its read
# leg is slower than at six. (tools/read_probe.py, PERF.md §6 PR 43.)
_CHUNK_READS = 6
# The least bytes of a chunk a thread of them is worth: a hop to a kept
# worker costs 0.05-0.1 ms, so a run of 1 MiB is slower in two pieces
# (0.24 ms for 0.18), one of 4 MiB level (0.50 / 0.46) and one of 16 MiB
# faster (1.40 in two, 1.22 in four, for 2.22): same probe.
_LEAST_READ = 4 << 20


@functools.cache
def _read_workers() -> ThreadPoolExecutor:
    """The threads that read beside the reader thread, kept by the process
    from the first chunk wide enough on, as `_copy_back_workers` are: not
    those, which work at the same moments on the fetch thread's behalf."""
    return ThreadPoolExecutor(max_workers=_CHUNK_READS - 1,
                              thread_name_prefix="ec-read")


def _cut_at_views(fd: int, offset: int, views: list, piece: int):
    """The read job ``(fd, offset, views)`` as consecutive jobs of whole
    views, each of ``piece`` bytes or the fewest views that reach them."""
    part, size = [], 0
    for v in views:
        part.append(v)
        size += len(v)
        if size >= piece:
            yield fd, offset, part
            offset, part, size = offset + size, [], 0
    if part:
        yield fd, offset, part


def _read_side_by_side(jobs: list) -> None:
    """The reads of ONE chunk, for a seal and a rebuild alike: every job
    ``(fd, offset, views)`` is a `_pread_into`, up to `_CHUNK_READS` of them
    at a time (`_side_by_side`), and all have ended when this returns. It
    adapts to what it is handed and to nothing else: as many threads as
    the jobs' bytes give `_LEAST_READ` each — so a small chunk is read in
    turn on this thread, as every chunk was — and a job of several views
    (a seal's run of neighbouring blocks) is cut at view boundaries into
    about equal pieces, one a thread, each with its own offset; a job of
    one view (a rebuild's row) is never cut. Counts the jobs it made
    against the stage span it runs in (``reads``); their bytes are the
    caller's to count, here on the reader thread: a worker has no span."""
    total = sum(len(v) for _, _, views in jobs for v in views)
    width = min(_CHUNK_READS, total // _LEAST_READ)
    if width > 1:
        piece = -(-total // width)
        jobs = [cut for job in jobs for cut in _cut_at_views(*job, piece)]
    trace.add_stage_count("reads", len(jobs))
    _side_by_side(_read_workers, lambda job: _pread_into(*job), jobs, width)


class _PoolClosed(Exception):
    """The pipeline wants no more chunks: raised to a reader that asks for
    (or waits for) a buffer after `_ChunkBuffers.close`."""


# Chunk buffers one call may have in flight, and idle ones the process keeps
# between calls: two, the one the reader fills and the one the legs after
# it hold (double buffering). Of the up to eight places of the overlap
# pipeline that can hold a chunk (four legs, four queue slots) only these
# are ever occupied: the reader waits for a buffer where it used to wait
# for a queue slot. Not more, by measurement, twice. PR 25 found 4 and 8
# slower when every buffer was a call's own and cost it a first touch.
# PR 37 took the first touch away (`_KeptBuffers`) and swept 2, 3 and 4
# again (v5e host, PERF.md §6 PR 37): a third chunk in flight is a third
# chunk on the link, and the copy back of one chunk's parity falls from
# 1.35-1.42 to 0.70-0.73 GB/s (0.54-0.62 at four) beside the staging of
# the next ones: the fetch leg goes from 73-75% to 85-87% busy of a seal's
# pipeline that comes out LONGER (0.82 s at two, 0.99 at three, 1.00 at
# four), a rebuild is level (one shard lost: 1,212 / 1,223 / 1,195 MB/s
# at the client, the median of three runs' medians), and the device holds
# 834 MiB for 574 at its peak. What would make a deeper pool pay is a
# copy back that is not slowed by the staging beside it, not more buffers.
# (Since PR 40 a chunk's copy back is 10-38 ms for 58-101, `_copy_back`;
# the depths have not been swept again: ROADMAP B3.)
_POOL_BUFFERS = 2


class _KeptBuffers:
    """The chunk buffers the process keeps while no call uses them.

    A 127 MiB ``np.empty`` lies above glibc's largest mmap threshold: it is
    fresh pages every time, given back to the kernel when freed, and a read
    into pages never touched runs at a third of the speed of one into
    pages that were (v5e host, PERF.md §6 PR 25: 0.86 against 2.31 GB/s).
    So a buffer a call has done with stays here, for the next seal or
    rebuild of any geometry and backend: at most `_POOL_BUFFERS` of them,
    whatever else comes back is freed. A daemon that has sealed holds up
    to that many of its largest chunk while idle."""

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: list[np.ndarray] = []

    def take(self, nbytes: int) -> Optional[np.ndarray]:
        """A kept buffer of at least ``nbytes``, or None: the caller
        allocates. One too small for this call is dropped, not handed on:
        the call's own takes its place when it comes back."""
        with self._lock:
            flat = self._idle.pop() if self._idle else None
        return flat if flat is not None and flat.nbytes >= nbytes else None

    def keep(self, flats: list) -> None:
        with self._lock:
            self._idle.extend(flats[: _POOL_BUFFERS - len(self._idle)])


_KEPT = _KeptBuffers()


class _ChunkBuffers:
    """One call's bounded share of the process's host buffers for
    (k, width) chunks.

    `take` hands out a C-contiguous ``(k, width)`` view of a recycled
    buffer; while the call has had fewer than ``count`` it takes one the
    process kept from an earlier call (`_KeptBuffers`) or allocates, and
    otherwise it waits until `give` brings one back: the reader's
    backpressure, and the bound on the host memory of a call's chunks.
    When the call ends (`close`) its buffers go to the kept list: the idle
    ones at once, and — once every leg has ended — the ones a failed
    pipeline dropped on its way out. A buffer
    comes back holding its last chunk — of this call or of another, of
    another volume, geometry or number of rows — and is NOT cleared, so
    whoever fills it writes every byte of the view. Each `take` leaves one
    stage in the tracer's table, ``<op>.buf.new`` (allocated) or
    ``<op>.buf.wait`` (recycled; ``busy_s`` is the wait, next to nothing
    for a kept one), with the buffer's ``bytes``."""

    def __init__(self, op: str, nbytes: int, count: int = _POOL_BUFFERS):
        self._op = op
        self._nbytes = nbytes  # of the largest chunk: any buffer fits any
        self._unmade = count
        self._free: list[np.ndarray] = []
        self._out: list[np.ndarray] = []  # taken and not given back yet
        self._cond = threading.Condition()
        self._closed = False

    def take(self, k: int, width: int) -> np.ndarray:
        t0 = time.perf_counter()
        with self._cond:
            while not (self._free or self._unmade or self._closed):
                self._cond.wait()
            if self._closed:
                raise _PoolClosed()
            if self._free:
                flat = self._free.pop()
            else:
                flat = None
                self._unmade -= 1
        how = "wait"
        if flat is None:
            flat = _KEPT.take(self._nbytes)
        if flat is None:
            how, flat = "new", np.empty(self._nbytes, dtype=np.uint8)
        trace.record_stage(f"{self._op}.buf.{how}",
                           time.perf_counter() - t0, bytes=flat.nbytes)
        with self._cond:
            self._out.append(flat)
        return flat[: k * width].reshape(k, width)

    def give(self, mat: np.ndarray) -> None:
        """Nothing reads ``mat`` (a `take`) any more: recycle its buffer,
        to this call's reader or, after `close`, to the process."""
        flat = mat.base
        with self._cond:
            self._out = [b for b in self._out if b is not flat]
            if not self._closed:
                self._free.append(flat)
                self._cond.notify()
                return
        _KEPT.keep([flat])

    def close(self, ended: bool = False) -> None:
        """The pipeline is ending: release a reader that waits in `take`,
        and hand the idle buffers to the process. ``ended``: every leg has
        returned, so a buffer still out belongs to a chunk that a failed
        pipeline dropped (a leg that failed gives nothing back), and goes
        with the idle ones."""
        with self._cond:
            self._closed = True
            idle, self._free = self._free, []
            if ended:
                idle, self._out = idle + self._out, []
            self._cond.notify_all()
        _KEPT.keep(idle)


def _work_items(
    dat_size: int, k: int, large_block_size: int, small_block_size: int, chunk: int
):
    """Work list covering the .dat in shard-file append order
    (encodeDatFile's large-then-small row walk). Two item kinds:

    - ``("cols", row_start, block_size, col, width)`` — one column slice of
      a row whose blocks exceed the chunk budget (the 1 GB large rows);
    - ``("rows", region_start, block_size, n_rows)`` — n_rows CONSECUTIVE
      rows batched into one device launch. Striping is row-major, so the
      region is a plain ``(n_rows, k, block)`` reshape: per-item width grows
      from one small block (1 MB) to the full chunk (32 MB), turning 10×
      strided 1 MB seeks per item into one sequential read and cutting
      launches + D2H transfers by chunk/block (the r3 e2e probe spent its
      whole wall on per-megabyte transfer latency). Output bytes are
      unchanged — batching is associativity of column-independent encode.
    """
    items = []
    remaining, processed = dat_size, 0
    n_large = 0
    while remaining > large_block_size * k:
        n_large += 1
        remaining -= large_block_size * k
    for _ in range(n_large):
        if large_block_size > chunk:
            for col in range(0, large_block_size, chunk):
                items.append(
                    ("cols", processed, large_block_size, col,
                     min(chunk, large_block_size - col))
                )
        else:
            items.append(("rows", processed, large_block_size, 1))
        processed += large_block_size * k
    n_small = 0
    while remaining > 0:
        n_small += 1
        remaining -= small_block_size * k
    if chunk < small_block_size:
        # budget below one block (scarce HBM): column slices per row keep
        # every launch within the budget, as the pre-batching code did
        for r in range(n_small):
            base = processed + r * small_block_size * k
            for col in range(0, small_block_size, chunk):
                items.append(
                    ("cols", base, small_block_size, col,
                     min(chunk, small_block_size - col))
                )
        return items
    rows_per = chunk // small_block_size
    r = 0
    while r < n_small:
        g = min(rows_per, n_small - r)
        items.append(
            ("rows", processed + r * small_block_size * k, small_block_size, g)
        )
        r += g
    return items


def _item_width(item) -> int:
    """Columns this work item contributes to every shard file."""
    if item[0] == "cols":
        return item[4]
    return item[2] * item[3]  # block_size * n_rows


def _item_dat_bytes(item, k: int, dat_size: int) -> int:
    """Bytes of the .dat this work item covers (the rest of its width is
    zero padding past EOF)."""
    if item[0] == "cols":
        _, start, block_size, col, width = item
        return sum(
            max(0, min(width, dat_size - (start + i * block_size + col)))
            for i in range(k)
        )
    _, start, block_size, g = item
    return max(0, min(start + g * k * block_size, dat_size) - start)


def _region_fully_data(fd: int, start: int, length: int) -> bool:
    """True when [start, start+length) contains no filesystem hole."""
    cur = os.lseek(fd, 0, os.SEEK_CUR)
    try:
        hole_off = os.lseek(fd, start, os.SEEK_HOLE)
    except (OSError, AttributeError, ValueError):
        return True  # no SEEK_HOLE support: everything reads as data
    finally:
        os.lseek(fd, cur, os.SEEK_SET)
    return hole_off >= start + length


def _item_segments(fd: int, item, k: int, dat_size: int) -> list:
    """Where a work item's data lies: ``(slot, offset, n)`` for each of its
    block segments that holds any — ``n`` bytes at ``offset`` of the .dat.
    Slot ``r * k + i`` of a "rows" item is row ``r`` of shard ``i``; a
    "cols" item has one slot a shard. Segments that are filesystem holes or
    lie past EOF are left out (they are zeros), so an empty list is a chunk
    of zeros: it needs no buffer and no encode."""
    dense = False
    if item[0] == "cols":
        _, start, block_size, col, width = item
        spots = [(i, start + i * block_size + col, width) for i in range(k)]
    else:
        _, start, block_size, g = item
        end = min(start + g * k * block_size, dat_size)
        if start >= dat_size or _is_hole(fd, start, end - start):
            return []
        # dense region (the common case): no lseek a segment. Mixed
        # data/holes (punched deletes in sealed volumes): per-block
        # SEEK_DATA skips keep the kernel from zero-filling the holes
        dense = _region_fully_data(fd, start, end - start)
        spots = [(s, start + s * block_size, block_size) for s in range(g * k)]
    segments = []
    for slot, offset, size in spots:
        n = min(size, dat_size - offset)
        if n > 0 and (dense or not _is_hole(fd, offset, n)):
            segments.append((slot, offset, n))
    return segments


def _read_item(fd: int, item, segments: list, mat: np.ndarray) -> None:
    """Fill ``mat``, the (k, width) matrix of one work item, in one pass:
    each of ``segments`` (`_item_segments`) is read from the file straight
    to where the kernel wants it, and what no segment covers is zeroed.

    A "rows" item's segment ``r * k + i`` — block ``i`` of row ``r``,
    ``block`` bytes of the .dat — lands in ``mat[i, r*block:(r+1)*block]``;
    a "cols" item's segment ``i`` in ``mat[i]``. Neighbours in the file are
    one run, and the chunk's runs are read side by side
    (`_read_side_by_side`: a long run in pieces), every byte once. ``mat``
    is a recycled buffer that holds an
    older chunk: hole segments, segments past EOF and the tail of the one
    that EOF cuts are zeroed here; nothing else is."""
    k = mat.shape[0]
    size = item[4] if item[0] == "cols" else item[2]
    slots = mat.reshape(k, -1, size)  # slot s is slots[s % k, s // k]
    wanted = {slot for slot, _, _ in segments}
    for s in range(k * slots.shape[1]):
        if s not in wanted:
            slots[s % k, s // k][:] = 0
    runs: list = []  # (offset, views): neighbours in the file, one read
    end = None
    for slot, offset, n in segments:
        dst = slots[slot % k, slot // k]
        dst[n:] = 0
        if offset != end:
            runs.append((offset, []))
        runs[-1][1].append(dst[:n])
        end = offset + n
    if runs:
        _read_side_by_side([(fd, offset, views) for offset, views in runs])


def _depth_chunk(chunk: int, total_width: int, floor: int, depth: int = 8) -> int:
    """Shrink the per-item column width so the overlap pipeline gets ~depth
    items: a 128 MB volume under the default 32 MB chunk collapses to ONE
    work item, and a single item overlaps nothing — r4's e2e efficiency was
    pinned at ~0.65 by exactly this (wall = read + H2D + kernel + D2H,
    serial). Rounds up to `floor` (the alignment/batching granularity) and
    never grows past the budgeted `chunk`; big volumes (total/depth ≥
    chunk) are unaffected."""
    target = -(-total_width // depth)
    target = max(floor, -(-target // floor) * floor)
    return max(min(chunk, target), min(chunk, floor))


def _budgeted_chunk(codec, chunk: int, device_streams: int) -> int:
    """Cap the column-chunk size against free device memory.

    The overlap pipeline keeps ≤3 chunks device-resident (one in compute,
    one in the fetch queue, one mid-fetch), each holding
    ~device_streams×chunk bytes in HBM (k input rows staged + output rows
    produced). Only a quarter of the reported free pool is budgeted;
    oversized chunks are split rather than dying with RESOURCE_EXHAUSTED
    (VERDICT r3 weak #1). A codec that reports no bound (host codecs, JAX
    on the CPU platform) keeps the requested chunk; a TPU that reports no
    allocator stats raises in device_memory_free."""
    free = codec.device_memory_free()
    if free is None:
        return chunk
    cap = free // (4 * 3 * max(1, device_streams))
    align = codec.alignment()
    cap = max(align, (cap // align) * align)
    return min(chunk, cap)


def plan_encode(
    codec,
    dat_size: int,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    chunk_bytes: Optional[int] = None,
) -> tuple[int, list]:
    """The encode work plan of `write_ec_files`: the column chunk and the
    work items (`_work_items`) that cover a .dat of ``dat_size`` bytes.

    Returns ``(chunk, items)``. An explicit ``chunk_bytes`` fixes the
    pipeline depth (no _depth_chunk re-split) but is still capped against
    free HBM — the caller owns the plan's shape, not its memory safety
    (rebuild_ec_files applies the same cap to explicit chunks)."""
    k = codec.data_shards
    chunk = chunk_bytes if chunk_bytes is not None else codec.chunk_bytes
    chunk = _budgeted_chunk(codec, chunk, k + codec.parity_shards)
    if chunk_bytes is None and chunk >= small_block_size:
        chunk = _depth_chunk(chunk, -(-dat_size // k), small_block_size)
    items = _work_items(dat_size, k, large_block_size, small_block_size, chunk)
    return chunk, items


class _HashedShards:
    """The shard files of one seal, each with a running SHA-256 of exactly
    the bytes that go into it, in the order they go: the sums of the .vif
    (`save_volume_info`), taken while a chunk's rows are in memory instead
    of by reading the staged files back.

    The rows of a chunk are independent — a file and a digest a shard,
    fourteen at RS(10,4), sixteen at RS(12,4) — and ``write`` and
    ``update`` both release the interpreter lock, so a
    pool that lives as long as the call takes them side by side, a row a
    task; the caller's thread waits for all of a chunk's rows, so a file
    and its digest see the chunks in order, and a recycled buffer is free
    when `append` returns. A row is handed to both as the one view it is:
    no copy. The pool is as wide as the rows and the host's cores allow
    (v5e host, 13 cores, PERF.md §6 PR 27: with one or two threads the
    writer bounds the pipeline, from four up a seal is no longer for it;
    the widest leaves that margin to hosts whose SHA-256 is slower)."""

    def __init__(self, outputs: list):
        self.name = outputs[0].name  # what a faultpoint of the seal names
        self._outputs = outputs
        self._digests = [hashlib.sha256() for _ in outputs]
        self._fed = [0] * len(outputs)  # bytes, a digest
        self._busy = [0.0] * len(outputs)  # seconds inside update, a digest
        self._zeros = np.zeros(0, dtype=np.uint8)
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, min(len(outputs), os.cpu_count() or 1)),
            thread_name_prefix="ec-hash",
        )

    def append(self, rows) -> None:
        """Row ``i`` of ``rows`` (one buffer a shard) goes to the end of
        file ``i`` and into digest ``i``."""
        self._each_row(self._append, rows)

    def skip(self, width: int) -> None:
        """A chunk of zeros: every file skips ``width`` bytes (the region
        stays a hole), every digest is fed them from one zero buffer."""
        if len(self._zeros) < width:
            # never written to: the pages are the kernel's one zero page
            self._zeros = np.zeros(width, dtype=np.uint8)
        zeros = self._zeros[:width]
        self._each_row(self._skip, [zeros] * len(self._outputs))

    def _append(self, sid: int, row) -> None:
        row = np.ascontiguousarray(row)
        self._outputs[sid].write(row)
        self._feed(sid, row)

    def _skip(self, sid: int, zeros) -> None:
        self._outputs[sid].seek(len(zeros), 1)
        self._feed(sid, zeros)

    def _feed(self, sid: int, row) -> None:
        t0 = time.perf_counter()
        self._digests[sid].update(row)
        self._busy[sid] += time.perf_counter() - t0
        self._fed[sid] += row.nbytes

    def _each_row(self, fn, rows) -> None:
        tasks = [self._pool.submit(fn, sid, row) for sid, row in enumerate(rows)]
        wait_all(tasks)  # all of them, also when one raised: no row in flight
        for task in tasks:
            task.result()

    def finish(self, final: int) -> list[str]:
        """Bring every file to its ``final`` size (trailing holes) and hand
        back the hex sums, one a shard. A digest that was not fed exactly
        the file's bytes raises: the scrub would take a sum over other
        bytes for a corrupt shard and start a rebuild. Leaves one
        ``ec.seal.hash`` stage a shard: ``busy_s`` the seconds inside its
        digest's updates — spent on the pool's threads, beside the other
        rows' and beside the pipeline's other legs — and the ``bytes`` fed."""
        for o, fed in zip(self._outputs, self._fed):
            o.truncate(final)
            if fed != final:
                raise RuntimeError(
                    f"{o.name}: digest fed {fed} bytes, "
                    f"the shard file holds {final}"
                )
        for sid, busy_s in enumerate(self._busy):
            trace.record_stage("ec.seal.hash", busy_s, sid=sid, bytes=final)
        return [d.hexdigest() for d in self._digests]

    def close(self) -> None:
        self._pool.shutdown(wait=True)
        for o in self._outputs:
            o.close()


def write_ec_files(
    base_file_name: str,
    codec: Optional[Codec] = None,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    chunk_bytes: Optional[int] = None,
    suffix: str = "",
) -> list[str]:
    """Generate all shard files from ``base.dat`` (WriteEcFiles, :57) and
    return their SHA-256 sums, hex, one a shard: each taken over the bytes
    of its file as they were written (`_HashedShards`).

    ``suffix`` — appended to every shard file name. The crash-safe commit
    path (Store.ec_encode_volume) passes ``".tmp"`` so the shard set is
    staged and only appears under its final names after the commit
    manifest is durable; the bare call writes final names directly (tools,
    tests).

    Every codec runs the 4-leg overlap pipeline (`_encode_pipelined`): a
    reader thread streams column chunks off disk, the main thread stages
    them (``codec.device_put``: into HBM, or nowhere for a host codec) and
    dispatches the encode (``codec.matmul_device``: an async kernel launch,
    or the host's matmul then and there), a fetch thread blocks on each
    chunk's parity (the D2H leg), and a writer thread appends the k+m shard
    files and feeds their digests, the rows of a chunk side by
    side. Disk read, H2D copy, compute, D2H and file writes for
    neighbouring chunks overlap — the reference's
    serial 256KB read→Encode→write loop (`ec_encoder.go:162-192`) turned into
    a pipeline sized for a TPU.

    A chunk is read ONCE, each block of the .dat straight to its place in
    a ``(k, width)`` matrix (`_read_item`: row ``i`` is the chunk's columns
    of shard ``i``), into one of the call's bounded share of the process's
    chunk buffers (`_ChunkBuffers`: kept from the seal or rebuild before
    where there was one). A buffer belongs to the reader until a chunk is
    read, then travels with the chunk through dispatch and fetch to the
    writer, which returns it to the pool once the data rows are in the
    shard files. A chunk of zeros (a hole, or past EOF) takes no buffer.
    """
    codec = codec or get_codec()
    k, m = codec.data_shards, codec.parity_shards
    dat = base_file_name + ".dat"
    dat_size = os.path.getsize(dat)
    _, items = plan_encode(
        codec, dat_size, large_block_size, small_block_size, chunk_bytes
    )

    shards = _HashedShards([
        open(base_file_name + shard_ext(i) + suffix, "wb")
        for i in range(k + m)
    ])
    try:
        _encode_pipelined(dat, items, codec, shards, dat_size)
        return shards.finish(
            ec_shard_base_size(dat_size, k, large_block_size, small_block_size)
        )
    finally:
        shards.close()


def _chunk_nbytes(items, k: int) -> int:
    """Bytes of the widest work item's (k, width) matrix."""
    return k * max(map(_item_width, items), default=0)


def _overlap_pipeline(produce, compute, consume, fetch,
                      op: str = "ec.overlap",
                      buffers: Optional[_ChunkBuffers] = None) -> None:
    """Four-stage overlap shared by encode and rebuild: a reader thread
    runs `produce` (an iterator of read jobs, one a chunk: each returns
    the host chunk), the main thread runs `compute` (async device
    dispatch: H2D + kernel launch), a fetch thread runs `fetch` (blocks on
    device results — the D2H leg), and a writer thread runs `consume`
    (writes files). Bounded queues give ~2 chunks of lookahead per edge;
    any stage failing drains the others so every thread exits and the
    first error is re-raised.

    The dedicated fetch leg is what lets H2D of chunk i+1 ride the link
    concurrently with D2H of chunk i (the transfer directions are
    independent); folding the blocking D2H into the writer (the r4 shape)
    left dispatch serialized behind it — wall was ~1.5× the slowest stage
    even with writes discarded.

    ``buffers`` is the pool the caller's chunks live in, if they do. The
    reader owns a buffer from the moment `produce` takes it (between two
    jobs, where it also waits for one: backpressure, as a full queue is)
    until the job has filled it; from then on it belongs to the chunk, and
    the caller's stage that is last to read it gives it back. The pipeline
    itself only closes the pool, on the first error of any leg and when it
    ends: a leg that failed gives nothing back — the pool takes what such a
    chunk held once every leg has returned — and a reader waiting for a
    buffer must end as a reader waiting for a queue slot does.

    Every chunk passes each leg inside a stage span (stats/trace.py):
    ``<op>.read``, ``.dispatch``, ``.fetch`` and ``.write`` under
    ``<op>.pipeline``, the call's wall. They time the stage callable alone
    (the read job, not `produce`'s step to it), not the queue blocking
    around it, and the callables count the bytes they move against them
    (``trace.add_stage_bytes``); the threads run in copies of the caller's
    context, so the spans of one seal are one tree. The totals are served
    in /status (``ec_codec.stages``): wall ≈ max(stage busy) rather than
    Σ(stages) is the whole point vs the reference's serial
    read→Encode→write loop (ec_encoder.go:162-192)."""
    import contextvars
    import queue

    # one-slot mid/out queues: enough lookahead for compute(i+1) to ride
    # the link concurrently with fetch(i), without tripling the chunks of
    # host+device memory the pipeline keeps resident
    read_q: queue.Queue = queue.Queue(maxsize=2)
    fetch_q: queue.Queue = queue.Queue(maxsize=1)
    write_q: queue.Queue = queue.Queue(maxsize=1)
    errors: list[BaseException] = []

    def fail(e: BaseException) -> None:
        errors.append(e)
        if buffers is not None:
            buffers.close()

    def run_leg(leg, fn, *got):
        """One chunk through one leg, inside that leg's stage span."""
        with trace.stage_span(f"{op}.{leg}"):
            return fn(*got)

    def reader():
        try:
            for job in produce():
                if errors:
                    return
                read_q.put(run_leg("read", job))
        except _PoolClosed:
            pass  # the error that closed the pool is the one to raise
        except BaseException as e:  # surfaced after join
            fail(e)
        finally:
            read_q.put(None)

    def fetcher():
        try:
            while True:
                got = fetch_q.get()
                if got is None:
                    return
                write_q.put(run_leg("fetch", fetch, got))
        except BaseException as e:
            fail(e)
            while fetch_q.get() is not None:  # drain so the feeder unblocks
                pass
        finally:
            write_q.put(None)

    def writer():
        try:
            while True:
                got = write_q.get()
                if got is None:
                    return
                run_leg("write", consume, got)
        except BaseException as e:
            fail(e)
            while write_q.get() is not None:  # drain so the feeder unblocks
                pass

    def thread(target) -> threading.Thread:
        # a copy of this context each (one Context is entered by one thread
        # at a time), taken inside the pipeline's span: the legs' spans
        # parent on it, as util/pipeline.py's do on their submitter's
        return threading.Thread(
            target=contextvars.copy_context().run, args=(target,), daemon=True
        )

    with trace.stage_span(f"{op}.pipeline", quiet=True):
        rt = thread(reader)
        wt = thread(writer)
        ft = thread(fetcher)
        rt.start()
        wt.start()
        ft.start()
        try:
            while True:
                got = read_q.get()
                if got is None:
                    break
                if errors:
                    continue  # keep draining so the reader can finish
                try:
                    fetch_q.put(run_leg("dispatch", compute, got))
                except BaseException as e:
                    fail(e)
        finally:
            fetch_q.put(None)
            ft.join()  # fetcher forwards its None to write_q on exit
            wt.join()
            # unblock the reader if it waits for a buffer or is mid-put
            # (main loop exited early)
            if buffers is not None:
                buffers.close()
            while rt.is_alive():
                try:
                    read_q.get_nowait()
                except queue.Empty:
                    rt.join(timeout=0.05)
            rt.join()
            if buffers is not None:
                buffers.close(ended=True)
        if errors:
            raise errors[0]


def _await(on_device) -> bool:
    """Wait until ``on_device`` is ready. False for a host codec's array:
    it is ready as it is, and on the host already."""
    ready = getattr(on_device, "block_until_ready", None)
    if ready is None:
        return False
    ready()
    return True


class _StagedWatch:
    """The host-to-device leg as a stage of its own, ``<op>.h2d``: from a
    chunk's ``device_put`` call until its staged input is ready. A thread of
    its own does the waiting, so that no wait is added on the dispatch
    thread (the pipeline's overlap is as it was), none behind the fetch
    thread's copy back (the time is the link's, not a queue's), and nothing
    keeps a chunk's input on the device longer than its kernel does: the
    watch lets go of it the moment it is ready."""

    def __init__(self, op: str):
        import queue
        import threading

        self._stage = f"{op}.h2d"
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="ec-h2d"
        )
        self._thread.start()

    def watch(self, staged, t_put: float) -> None:
        """Called by the dispatch thread between ``device_put`` and the
        launch; the span parents on the dispatch span it is called under."""
        import contextvars

        self._q.put((contextvars.copy_context(), staged, t_put))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            ctx, staged, t_put = item
            try:
                _await(staged)
            except Exception as e:
                # the fetch leg meets the same error and raises it
                glog.warning("%s: staged input never ready: %s", self._stage, e)
                continue
            ctx.run(trace.record_stage, self._stage,
                    time.perf_counter() - t_put, bytes=staged.nbytes)
            del item, ctx, staged  # not held while waiting for the next

    def close(self) -> None:
        self._q.put(None)
        self._thread.join()


# Transfers of one chunk's result the copy back puts side by side: its
# only parameter. A row of a chunk's result is 8-13 MiB at the sizes the
# planner makes; four of them in turn take 28-71 ms on a v5e host and four
# side by side 9-11 (13 beside the next chunk's staging), and cutting a
# row into column pieces gains nothing (tools/d2h_probe.py, PERF.md §6 PR 40).
_COPY_BACK_TRANSFERS = 4


@functools.cache
def _copy_back_workers() -> ThreadPoolExecutor:
    """The threads that copy rows back, kept by the process from the first
    device result on, as `_KeptBuffers` keeps the chunk buffers: a seal or
    a rebuild starts none and ends none."""
    return ThreadPoolExecutor(max_workers=_COPY_BACK_TRANSFERS - 1,
                              thread_name_prefix="ec-copy-back")


def _copy_back(op: str, out_dev) -> list:
    """The only place where a chunk's result leaves the device: the fetch
    thread's part of a seal and of a rebuild. Waits for the
    ``(rows, width)`` result, then brings it to the host as a stage of its
    own, ``<op>.d2h`` (``bytes`` the result's, ``transfers`` the
    device-to-host copies it took), and returns the rows as host arrays.

    A row comes back as a 1-D array of its own (``np.asarray``), the rows
    side by side (`_side_by_side`: the last few on this thread, the others
    on the kept workers), never the 2-D result as one: on the chip a
    ``uint8[R, width]`` lies in tiles of four rows, so with one row the
    transfer moves four, and either way one transfer fills one fresh
    allocation on one thread, at the price of pages never touched —
    51-75 ms a chunk of 126.9 MiB whatever R, where a row is 4-5 ms and
    four side by side 11 (v5e host, PERF.md §6 PR 40). It adapts to what
    it is handed and to nothing else: the rows are in ``out_dev.shape``; a
    host codec's result has no ``block_until_ready``, is on the host
    already and is handed on as it is, no thread and no copy; a row of a
    result sharded over several devices is gathered from them by the same
    ``np.asarray`` (a device's piece at a time instead reads 7-11 ms a
    chunk for the rows' 14-16 when probed alone and three times the rows'
    inside the pipeline: PERF.md §6 PR 40, four chips)."""
    rows = out_dev.shape[0] if _await(out_dev) else 0  # to transfer
    with trace.stage_span(f"{op}.d2h", bytes=out_dev.nbytes, transfers=rows):
        out = (_side_by_side(_copy_back_workers, np.asarray,
                             [out_dev[j] for j in range(rows)],
                             _COPY_BACK_TRANSFERS)
               if rows else list(out_dev))
    trace.add_stage_bytes(out_dev.nbytes)
    return out


def _encode_pipelined(dat, items, codec, shards: _HashedShards,
                      dat_size: int) -> None:
    """`write_ec_files` through the overlap pipeline. A chunk's buffer is
    the reader's while it is filled, then the chunk's: dispatch stages it,
    fetch awaits the parity (so the staged input has been consumed), and
    the writer, last to read it (its rows go to the files and their
    digests, `_HashedShards.append`), gives it back to the pool."""
    k, m = codec.data_shards, codec.parity_shards
    align = codec.alignment()
    buffers = _ChunkBuffers("ec.seal", _chunk_nbytes(items, k))

    def read_chunk(fd, it, segments, data):
        if data is not None:
            _read_item(fd, it, segments, data)
        trace.add_stage_bytes(_item_dat_bytes(it, k, dat_size))
        return _item_width(it), data

    def produce():
        with open(dat, "rb") as f:
            fd = f.fileno()
            for it in items:
                segments = _item_segments(fd, it, k, dat_size)
                data = buffers.take(k, _item_width(it)) if segments else None
                yield functools.partial(read_chunk, fd, it, segments, data)

    def compute(got):
        width, data = got
        if data is None or not data.any():
            return width, data, None  # zero chunk: parity is zeros, skip device
        piece = data
        if width % align:
            padded = align * -(-width // align)
            piece = np.pad(data, ((0, 0), (0, padded - width)))
        t_put = time.perf_counter()
        staged = codec.device_put(piece)
        h2d.watch(staged, t_put)
        trace.add_stage_bytes(piece.nbytes)
        return width, data, codec.matmul_device(codec.parity_rows, staged)

    def fetch(got):
        width, data, parity_dev = got
        if parity_dev is None:
            return width, data, None
        # the blocking D2H leg: overlaps the next chunk's H2D + dispatch
        return width, data, _copy_back("ec.seal", parity_dev)

    def consume(got):
        faultpoints.fire("ec.encode.chunk", path=shards.name)
        width, data, parity = got
        if parity is None:
            shards.skip(width)  # keep sparse regions sparse (holes)
        else:
            shards.append([*data, *(row[:width] for row in parity)])
            trace.add_stage_bytes((k + m) * width)
        if data is not None:
            buffers.give(data)

    h2d = _StagedWatch("ec.seal")
    try:
        _overlap_pipeline(produce, compute, consume, fetch=fetch,
                          op="ec.seal", buffers=buffers)
    finally:
        h2d.close()


def rebuild_ec_files(
    base_file_name: str,
    codec: Optional[Codec] = None,
    chunk_bytes: Optional[int] = None,
    wanted: Optional[list[int]] = None,
) -> list[int]:
    """Regenerate missing shard files — all of them, or the ``wanted`` ones
    among them — from the fewest present ones that determine them
    (RebuildEcFiles / generateMissingEcFiles, :61,95; the read set is
    `Codec.plan`'s: the first k present of an RS volume, the six others of
    its local group for a shard an LRC(12,2,2) volume lost alone). Returns
    the generated ids; a loss the code does not decode raises
    `codec.Undecodable` and writes nothing."""
    codec = codec or get_codec()
    total = codec.total_shards
    chunk = chunk_bytes if chunk_bytes is not None else codec.chunk_bytes
    chunk = _budgeted_chunk(codec, chunk, total)

    present: dict[int, str] = {}
    missing: list[int] = []
    for sid in range(total):
        path = base_file_name + shard_ext(sid)
        if os.path.exists(path):
            present[sid] = path
        elif wanted is None or sid in wanted:
            missing.append(sid)
    if not missing:
        return []
    # one record a rebuild: how many shards it reads, and whether the lost
    # shards' own local groups sufficed
    with trace.stage_span("ec.rebuild.plan", width=0, local=0) as span:
        plan = codec.plan(missing, sorted(present))
        if span is not None:
            span.tags.update(width=len(plan.read), local=int(plan.local))

    sizes = {os.path.getsize(p) for p in present.values()}
    if len(sizes) != 1:
        raise ValueError(f"ec shard sizes disagree: {sizes}")
    shard_size = sizes.pop()

    ins = [open(present[sid], "rb") for sid in plan.read]
    outs = {sid: open(base_file_name + shard_ext(sid), "wb") for sid in missing}
    try:
        _rebuild_pipelined(
            codec, ins, outs, plan.matrix, shard_size,
            _depth_chunk(chunk, shard_size, codec.alignment()),
        )
        for sid in missing:
            outs[sid].truncate(shard_size)
    finally:
        for fh in ins:
            fh.close()
        for fh in outs.values():
            fh.close()
    return missing


def _rebuild_pipelined(codec, ins, outs, rows, shard_size, chunk) -> None:
    """`rebuild_ec_files` through the overlap pipeline: disk reads, H2D
    staging + device matmul, and shard writes of neighbouring chunks
    overlap, as a seal's do. ``ins`` are the files of the plan's read set,
    ``rows`` its matrix over them, ``outs`` the files it rebuilds, by id.

    Row ``r`` of a chunk's ``(len(ins), padded)`` buffer is read straight
    from the ``r``-th file of the read set, the rows side by side
    (`_read_side_by_side`, a row a job). The buffer is not carried past
    the device, so the fetch leg gives it back to the pool once the
    chunk's RESULT is ready and copied back, never at ``device_put``
    (and not before the copy back: a buffer given earlier is a third chunk
    staged on the device while the copy still runs): JAX keeps a host
    array immutable until it is transferred, and on the CPU platform the
    staged input may be the numpy memory itself."""
    n_read = len(ins)
    align = codec.alignment()
    widest = min(chunk, shard_size)
    buffers = _ChunkBuffers("ec.rebuild", n_read * -(-widest // align) * align)

    def read_chunk(pos, width, held, buf):
        if buf is None:
            return width, None
        buf[:, width:] = 0  # the alignment tail: zeros encode to zeros
        for row in range(n_read):
            if row not in held:
                buf[row, :width] = 0  # a hole: not read, and not left stale
        _read_side_by_side([(ins[row].fileno(), pos, [buf[row, :width]])
                            for row in held])
        trace.add_stage_bytes(width * len(held))
        return width, buf

    def produce():
        pos = 0
        while pos < shard_size:
            width = min(chunk, shard_size - pos)
            held = [row for row, fh in enumerate(ins)
                    if not _is_hole(fh.fileno(), pos, width)]
            buf = (buffers.take(n_read, -(-width // align) * align)
                   if held else None)
            yield functools.partial(read_chunk, pos, width, held, buf)
            pos += width

    def compute(got):
        width, buf = got
        if buf is None or not buf.any():
            return width, buf, None  # zeros reconstruct to zeros
        t_put = time.perf_counter()
        staged = codec.device_put(buf)
        h2d.watch(staged, t_put)
        trace.add_stage_bytes(buf.nbytes)
        return width, buf, codec.matmul_device(rows, staged)

    def fetch(got):
        width, buf, out_dev = got
        out = None
        if out_dev is not None:
            # blocking D2H leg
            out = _copy_back("ec.rebuild", out_dev)
        if buf is not None:
            buffers.give(buf)
        return width, out

    def consume(got):
        width, out = got
        if out is None:
            for fh in outs.values():
                fh.seek(width, 1)
            return
        for row, fh in zip(out, outs.values()):  # in the plan's wanted order
            fh.write(row[:width])
        trace.add_stage_bytes(len(outs) * width)

    h2d = _StagedWatch("ec.rebuild")
    try:
        _overlap_pipeline(produce, compute, consume, fetch=fetch,
                          op="ec.rebuild", buffers=buffers)
    finally:
        h2d.close()


# -- .ecx sorted index -------------------------------------------------------
def write_sorted_file_from_idx(
    base_file_name: str, ext: str = ".ecx", offset_size: int = OFFSET_SIZE
) -> None:
    """.idx → ascending-key sorted .ecx (WriteSortedFileFromIdx, :27-55).

    Replays the append-ordered .idx with latest-wins semantics (deletes drop
    the key), then writes entries in ascending key order.
    """
    entries: dict[int, tuple[int, int]] = {}
    with open(base_file_name + ".idx", "rb") as f:
        for key, offset, size in idx_mod.iter_index_file(f, offset_size):
            if offset != 0 and size != TOMBSTONE_FILE_SIZE:
                entries[key] = (offset, size)
            else:
                entries.pop(key, None)
    with open(base_file_name + ext, "wb") as out:
        for key in sorted(entries):
            offset, size = entries[key]
            out.write(idx_mod.pack_entry(key, offset, size, offset_size))


# -- .vif volume info --------------------------------------------------------
def save_volume_info(
    file_name: str,
    version: int = 3,
    replication: str = "",
    shard_sums: "list[str] | None" = None,
    geometry: "Geometry | None" = None,
) -> None:
    """jsonpb-style VolumeInfo (pb/volume_info.go:56 SaveVolumeInfo).

    ``shard_sums`` (sha256 hex per shard id, written at encode time) gives
    the background scrub a ground truth for shard integrity: RS encoding is
    deterministic, so a rebuilt shard hashes identically and the sums stay
    valid across rebuilds and copies (the .vif travels with the shards).
    ``geometry`` (``data_shards`` / ``parity_shards`` and, for a local
    reconstruction code, ``local_parity_shards``) is the code the volume
    was sealed at: with the shards it travels to every holder, and every
    later read, rebuild, copy and decode takes it from here
    (`volume_geometry`)."""
    info = {"files": [], "version": version, "replication": replication}
    if shard_sums is not None:
        info["shard_sums"] = shard_sums
    if geometry is not None:
        info.update(geometry.volume_info())
    with open(file_name, "w") as f:
        f.write(json.dumps(info, indent=2))


def load_volume_info(file_name: str) -> dict:
    if not os.path.exists(file_name):
        return {"files": [], "version": 0, "replication": ""}
    with open(file_name) as f:
        return json.load(f)


def volume_geometry(base_file_name: str) -> Geometry:
    """The geometry of the EC volume at ``base_file_name``, as its .vif
    records it; RS(10,4) where the .vif names none or is not there."""
    return Geometry.of_volume_info(load_volume_info(base_file_name + ".vif"))


def ec_shard_base_size(
    dat_size: int,
    data_shards: int,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
) -> int:
    """Size every shard file will have for a given .dat size."""
    k = data_shards
    n_large = 0
    remaining = dat_size
    while remaining > large_block_size * k:
        n_large += 1
        remaining -= large_block_size * k
    n_small = 0
    while remaining > 0:
        n_small += 1
        remaining -= small_block_size * k
    return n_large * large_block_size + n_small * small_block_size
