"""Vectorized S3-Select scan engine: fused filter+project plans over
uint8 byte batches.

``engine.py`` evaluates one document at a time — csv.DictReader, a dict
per row, a Python filter walk per row.  At warm-store scan sizes (ROADMAP
item 4: "S3 Select-class scans as a new workload") that caps out around
3 MB/s/core.  This module compiles the same filter dicts that ``sql.py``
emits into columnar plans that run the EC pattern end to end: stage
bytes → structural index → device batches → fused predicate kernel →
stream matched rows out.

Pipeline per CSV batch (the columnar format; the one the kernels cover):

1. **Structural indexing** (host numpy): newline and delimiter positions
   via dense byte compares + ``flatnonzero``/``searchsorted`` — one
   memory-bound pass that replaces the per-character csv state machine.
2. **Field extraction**: each referenced column becomes a padded
   ``[rows, width]`` uint8 matrix + length vector (a single fancy-index
   gather), the byte-batch layout the kernels consume.
3. **Fused predicate evaluation**: the whole WHERE tree — numeric
   compares, equality, lexicographic ordering, contains / starts_with —
   is one compiled function per plan.  The jax backend jit-compiles it
   (XLA, on the host CPU device — see ``scan_device``), the numpy
   fallback runs the identical expression graph eagerly.  Backend selection mirrors
   ``ec/codec.get_codec``: ``$SWEED_QUERY_BACKEND`` overrides, else jax
   if importable, else numpy.

Byte-identity with ``engine.run_query`` on EVERY input is the contract
(the property test in tests/test_query_scan.py enforces it).  The
kernels therefore compute a *validity* mask alongside the match mask:
any row whose bytes the kernel cannot decide with engine-exact semantics
— quoted CSV fields, ``\\r`` line endings, non-ASCII bytes, numeric
strings outside the simple ``-?\\d+(\\.\\d+)?``/15-digit exact-float
domain, fields longer than the kernel width cap, general LIKE patterns —
is re-evaluated through ``engine._matches`` in a row-at-a-time exact
lane.  JSON input takes the exact lane entirely (vectorized newline
segmentation only); a JSON array document degenerates to the engine,
kept only for protocol completeness.  The kernel/fallback split is
observable: ``sweed_query_*`` counters in ``stats/metrics.py``.

Exactness notes (why the kernel domain is what it is):

- Numeric parse folds ≤15 digits into a float64 mantissa (≤ 2^53, every
  intermediate exact) and divides by an exact power of ten — IEEE
  division rounds correctly, so the kernel float equals ``float(s)``.
  Anything float() might also accept ("+5", "1e3", "nan", "٥", "1_0",
  padded whitespace) is detected by charset and routed exact.
- UTF-8 is order-preserving, so lexicographic *byte* compare equals
  Python's codepoint compare for valid UTF-8; rows with any byte ≥ 0x80
  go exact instead of proving validity (replacement-char folding under
  ``errors="replace"`` can alias distinct byte strings).
- A double quote anywhere makes newlines untrustworthy as record breaks
  (quoted fields may embed them), so scanning switches to the exact csv
  parser from the first line containing one — records fully terminated
  before the first quote are provably unaffected and stay vectorized.
"""

from __future__ import annotations

import contextlib
import csv
import io
import json
import os
from typing import Any, Iterable, Iterator, Optional

import numpy as np

from ..stats.metrics import QUERY_COUNTERS
from ..util import glog
from . import engine as _engine

_MAX_FIELD_W = 512  # fields longer than this go to the exact lane
_ROW_BATCH = 1 << 17  # rows per device batch (bounds device mats ~64 MB)
_CMP_OPS = ("=", "!=", "<", "<=", ">", ">=")

# bytes float() could possibly accept somewhere in a number:
# digits, sign/exponent/dot/underscore, inf/nan letters, ascii whitespace
_FLOATISH = np.zeros(256, dtype=bool)
for _b in b"0123456789eE+-._ \t\n\r\x0b\x0cinfatyINFATY":
    _FLOATISH[_b] = True

# exact powers of ten for the ≤15-digit mantissa domain
_TEN_POWS = [10.0 ** k for k in range(16)]


def _pow2(n: int, floor: int = 8) -> int:
    v = floor
    while v < n:
        v <<= 1
    return v


def _want_float(want: Any) -> Optional[float]:
    """float(want) under engine._coerce_pair rules (bools are not
    numbers), or None when the engine would fall back to strings."""
    if isinstance(want, bool):
        return None
    try:
        return float(want)
    except (TypeError, ValueError):
        return None


# --------------------------------------------------------------------------
# kernel primitives — parametrized on xp (numpy | jax.numpy) so the same
# expression graph is the eager fallback AND the jitted kernel body
# --------------------------------------------------------------------------


def _colmask(xp, w, lens):
    return xp.arange(w)[None, :] < lens[:, None]


def _ascii_ok(xp, mat, lens):
    return ~xp.any((mat >= 128) & _colmask(xp, mat.shape[1], lens), axis=1)


def _numeric(xp, mat, lens):
    """→ (vals float64, simple, def_not_float): exact float values where
    the field matches the simple-number domain; a proof that float()
    must fail where the charset says so; everything else is neither and
    belongs to the exact lane."""
    n, w = mat.shape
    cm = _colmask(xp, w, lens)
    isdig = (mat >= 48) & (mat <= 57) & cm
    isdot = (mat == 46) & cm
    neg = (lens > 0) & (mat[:, 0] == 45)
    body0 = xp.where(neg, 1, 0)
    bodymask = cm & (xp.arange(w)[None, :] >= body0[:, None])
    digits = xp.sum(isdig, axis=1)
    dots = xp.sum(isdot, axis=1)
    pattern = xp.all(isdig | isdot | ~bodymask, axis=1)
    first_ix = xp.minimum(body0, w - 1)
    last_ix = xp.maximum(lens - 1, 0)
    first_dig = xp.take_along_axis(isdig, first_ix[:, None], axis=1)[:, 0]
    last_dig = xp.take_along_axis(isdig, last_ix[:, None], axis=1)[:, 0]
    simple = (
        pattern
        & (dots <= 1)
        & (digits >= 1)
        & (digits <= 15)
        & first_dig
        & last_dig
        & (lens > body0)
    )
    # positional digit sum: weight each digit by 10^(digits to its
    # right).  Every term and every partial sum is an integer ≤ 10^15 <
    # 2^53, so the float64 sum is exact in any order.
    right = xp.cumsum(isdig[:, ::-1], axis=1)[:, ::-1] - isdig
    weight = xp.asarray(_TEN_POWS, dtype=xp.float64)[xp.clip(right, 0, 15)]
    digval = xp.where(isdig, (mat & 0x0F).astype(xp.float64), 0.0)
    val = xp.sum(digval * weight, axis=1)
    dotpos = xp.argmax(isdot, axis=1)
    frac = xp.where(dots > 0, lens - 1 - dotpos, 0)
    scale = xp.asarray(_TEN_POWS, dtype=xp.float64)[xp.clip(frac, 0, 15)]
    vals = xp.where(neg, -1.0, 1.0) * val / scale
    floatish = xp.asarray(_FLOATISH)[mat] | ~cm
    def_not_float = (lens == 0) | ~xp.all(floatish, axis=1)
    return vals, simple, def_not_float


def _eq_bytes(xp, mat, lens, nb):
    m = len(nb)
    if m > mat.shape[1]:
        return xp.zeros(mat.shape[0], dtype=bool)
    needle = xp.asarray(np.frombuffer(nb, np.uint8))
    return (lens == m) & xp.all(mat[:, :m] == needle[None, :], axis=1)


def _lex_lt_eq(xp, mat, lens, nb):
    """(field < needle, field == needle) by byte order — equals Python
    str ordering for valid UTF-8 on both sides."""
    m = len(nb)
    n, w = mat.shape
    if m == 0:
        return xp.zeros(n, dtype=bool), lens == 0
    L = min(w, m)
    needle = xp.asarray(np.frombuffer(nb[:L], np.uint8))
    rng = xp.arange(L)[None, :]
    validj = rng < xp.minimum(lens, m)[:, None]
    mm = validj & (mat[:, :L] != needle[None, :])
    has = xp.any(mm, axis=1)
    ix = xp.argmax(mm, axis=1)
    fb = xp.take_along_axis(mat[:, :L], ix[:, None], axis=1)[:, 0]
    lt = xp.where(has, fb < needle[ix], lens < m)
    eq = ~has & (lens == m)
    return lt, eq


def _prefix(xp, mat, lens, nb):
    m = len(nb)
    if m > mat.shape[1]:
        return xp.zeros(mat.shape[0], dtype=bool)
    needle = xp.asarray(np.frombuffer(nb, np.uint8))
    return (lens >= m) & xp.all(mat[:, :m] == needle[None, :], axis=1)


def _substr(xp, mat, lens, nb):
    m = len(nb)
    n, w = mat.shape
    if m > w:
        return xp.zeros(n, dtype=bool)
    needle = xp.asarray(np.frombuffer(nb, np.uint8))
    acc = xp.zeros(n, dtype=bool)
    for o in range(w - m + 1):
        seg = xp.all(mat[:, o : o + m] == needle[None, :], axis=1)
        acc = acc | ((lens >= o + m) & seg)
    return acc


# --------------------------------------------------------------------------
# predicate-tree compiler: filter dict → fn(mats, lens, press) → (match,
# valid).  Traced once per plan by jax.jit (or run eagerly by numpy).
# --------------------------------------------------------------------------


def _build_node(flt, index, kern):
    xp = kern.xp
    if not flt:
        return lambda env, n: (
            xp.ones(n, dtype=bool),
            xp.ones(n, dtype=bool),
        )
    # key precedence mirrors engine._matches exactly
    if "and" in flt:
        kids = [_build_node(f, index, kern) for f in flt["and"]]

        def f_and(env, n):
            ms, vs = zip(*[k(env, n) for k in kids]) if kids else ((), ())
            if not kids:
                return xp.ones(n, dtype=bool), xp.ones(n, dtype=bool)
            all_valid = vs[0]
            definite_false = vs[0] & ~ms[0]
            match = ms[0]
            for mm, vv in zip(ms[1:], vs[1:]):
                all_valid = all_valid & vv
                definite_false = definite_false | (vv & ~mm)
                match = match & mm
            return match & all_valid, all_valid | definite_false

        return f_and
    if "or" in flt:
        kids = [_build_node(f, index, kern) for f in flt["or"]]

        def f_or(env, n):
            if not kids:
                return xp.zeros(n, dtype=bool), xp.ones(n, dtype=bool)
            ms, vs = zip(*[k(env, n) for k in kids])
            all_valid = vs[0]
            definite_true = vs[0] & ms[0]
            for mm, vv in zip(ms[1:], vs[1:]):
                all_valid = all_valid & vv
                definite_true = definite_true | (vv & mm)
            return definite_true, all_valid | definite_true

        return f_or
    if "not" in flt:
        kid = _build_node(flt["not"], index, kern)

        def f_not(env, n):
            mm, vv = kid(env, n)
            return ~mm & vv, vv

        return f_not
    return _build_leaf(flt, index, kern)


def _build_leaf(flt, index, kern):
    xp = kern.xp
    op = flt.get("op", "=")
    field = flt.get("field", "")
    want = flt.get("value")
    fi = index[field]

    if op in ("contains", "starts_with"):
        wb = str(want or "").encode("utf-8")
        if not wb:
            # '' is a substring/prefix of everything, missing fields
            # included (str(got or "") == "")
            return lambda env, n: (
                xp.ones(n, dtype=bool),
                xp.ones(n, dtype=bool),
            )
        search = _substr if op == "contains" else _prefix

        def f_str(env, n):
            mat, lens, present = env[fi]
            # missing rows have lens 0 → no match for a nonempty needle,
            # which is definitive; high-byte rows go exact
            return search(xp, mat, lens, wb), _ascii_ok(xp, mat, lens) | ~present

        return f_str

    if op in _CMP_OPS:
        wf = _want_float(want)
        ws = str(want).encode("utf-8")

        def str_cmp(mat, lens):
            # =/!= only need byte equality — the full lexicographic
            # first-diff kernel (argmax + gather) is for the orderings
            if op in ("=", "!="):
                eq = _eq_bytes(xp, mat, lens, ws)
                return eq if op == "=" else ~eq
            lt, eq = _lex_lt_eq(xp, mat, lens, ws)
            return _pick_cmp(xp, op, lt, eq)

        def f_cmp(env, n):
            mat, lens, present = env[fi]
            ascii_ok = _ascii_ok(xp, mat, lens)
            if wf is None:
                return str_cmp(mat, lens) & present, ascii_ok | ~present
            vals, simple, not_float = _numeric(xp, mat, lens)
            num_match = _num_cmp(xp, op, vals, wf)
            # string-compare fallback rows (engine: float(got) raised,
            # str-vs-str ordering applies) are provably the valid &
            # ~simple & present ones; in the common all-numeric column
            # there are none, so the lex kernel is skipped at runtime
            need_str = not_float & ascii_ok & present
            str_match = kern.cond(
                xp.any(need_str),
                lambda: str_cmp(mat, lens),
                lambda: xp.zeros(mat.shape[0], dtype=bool),
            )
            match = xp.where(simple, num_match, str_match)
            valid = simple | (not_float & ascii_ok)
            # engine: got is None → False before any coercion
            return match & present, valid | ~present

        return f_cmp

    # "like" and unknown ops: every PRESENT row goes to the exact lane
    # (engine raises ValueError there for unknown ops, exactly as
    # run_query would); missing rows are a definitive False — the
    # engine's `got is None` check fires before op dispatch.
    def f_exact(env, n):
        _, _, present = env[fi]
        return xp.zeros(n, dtype=bool), ~present

    return f_exact


def _pick_cmp(xp, op, lt, eq):
    if op == "=":
        return eq
    if op == "!=":
        return ~eq
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return ~(lt | eq)
    return ~lt  # >=


def _num_cmp(xp, op, vals, wf):
    if op == "=":
        return vals == wf
    if op == "!=":
        return vals != wf
    if op == "<":
        return vals < wf
    if op == "<=":
        return vals <= wf
    if op == ">":
        return vals > wf
    return vals >= wf


def _leaf_fields(flt, out):
    if not flt:
        return out
    if "and" in flt:
        for f in flt["and"]:
            _leaf_fields(f, out)
    elif "or" in flt:
        for f in flt["or"]:
            _leaf_fields(f, out)
    elif "not" in flt:
        _leaf_fields(flt["not"], out)
    else:
        out.append(flt.get("field", ""))
    return out


# --------------------------------------------------------------------------
# backends — selected like the EC path (ec/codec.get_codec)
# --------------------------------------------------------------------------


class NumpyKernels:
    """Eager numpy evaluation of the same expression graph the jax
    backend traces — the fallback for jax-less hosts and the bench's
    mid-tier comparison point."""

    name = "numpy"
    pads_batches = False  # eager: no retrace cost, no padding needed

    def __init__(self):
        self.xp = np

    def compile(self, fn, static_argnums=()):
        return fn

    def cond(self, pred, tfn, ffn):
        return tfn() if pred else ffn()

    def stage(self, buf: np.ndarray) -> np.ndarray:
        return buf

    def to_host(self, x):
        return np.asarray(x)


class JaxKernels:
    """jit-compiled fused predicate kernels (XLA).  x64 is required — the
    numeric-compare kernel's exactness proof lives in float64 mantissa
    arithmetic — but it is scoped to this class's own calls: the flag is
    part of every jit cache key, and turning it on for the whole process
    makes the EC Pallas kernel's index maps i64, which Mosaic rejects, so
    one /_query would take the daemon's EC path down."""

    pads_batches = True  # pow2 row buckets bound the jit retrace count

    def __init__(self):
        from ..util.jaxenv import import_jax  # ImportError → numpy upstream

        # host_only: a process that has not been given the chip must not
        # open it for a query (jax.devices("cpu") opens every backend)
        self._jax = jax = import_jax(host_only=True)
        self.xp = jax.numpy
        self.device = scan_device(jax)
        self.name = f"jax-{self.device.platform}"

    @contextlib.contextmanager
    def _scope(self):
        """x64 on and the scan's device as default, for this thread and
        this call only."""
        with self._jax.enable_x64(True), self._jax.default_device(self.device):
            yield

    def compile(self, fn, static_argnums=()):
        jitted = self._jax.jit(fn, static_argnums=static_argnums)

        def call(*args):
            with self._scope():
                return jitted(*args)

        return call

    def cond(self, pred, tfn, ffn):
        """Runtime branch inside a traced kernel — lets a plan skip the
        lexicographic fallback compare when no row in the batch needs it
        (the common all-numeric-column case)."""
        return self._jax.lax.cond(pred, tfn, ffn)

    def stage(self, buf: np.ndarray):
        """Move a segment's byte buffer to the device once, pow2-padded
        so batch calls against it hit a bounded set of traced shapes."""
        cap = _pow2(len(buf), 1 << 16)
        if cap != len(buf):
            grown = np.zeros(cap, dtype=np.uint8)
            grown[: len(buf)] = buf
            buf = grown
        return self._jax.device_put(buf, self.device)

    def to_host(self, x):
        return np.asarray(x)


def scan_device(jax):
    """The device scan kernels run on: the host CPU, whatever else the
    process holds.  The numeric kernel's byte-identity with
    ``engine.run_query`` needs correctly rounded float64 division
    (module docstring), and a TPU emulates f64: on a v5e 102,807 of
    199,401 simple decimals came out different from ``float(s)``
    ("191.6722" → 191.67219999999998; chip run of PR 21, CHANGES.md).
    So a query never computes on the chip, and says so in its backend
    label (``jax-cpu``)."""
    return jax.devices("cpu")[0]


_BACKENDS = {
    "numpy": NumpyKernels,
    "jax": JaxKernels,
    "cpu": JaxKernels,  # old spelling of jax
}


def get_kernels(backend: Optional[str] = None):
    """SWEED_QUERY_BACKEND=numpy|jax(|cpu) overrides; default is jax
    when importable, numpy otherwise — the ec/codec.get_codec shape."""
    if backend is None:
        backend = os.environ.get("SWEED_QUERY_BACKEND", "")
    backend = (backend or "").strip().lower()
    if backend and backend != "auto":
        try:
            cls = _BACKENDS[backend]
        except KeyError:
            raise ValueError(
                f"unknown query backend {backend!r} "
                f"(want one of {sorted(_BACKENDS)})"
            ) from None
        try:
            return cls()
        except ImportError:
            glog.warning("query backend %s unavailable; using numpy", backend)
            return NumpyKernels()
    try:
        return JaxKernels()
    except ImportError:
        return NumpyKernels()


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------


class ScanPlan:
    """One compiled filter+project plan.  Thread-compatible, not
    thread-safe: run one scan at a time per plan (each daemon request
    compiles its own — compilation is cheap next to the scan)."""

    def __init__(
        self,
        select: Optional[list] = None,
        where: Optional[dict] = None,
        limit: int = 0,
        input_format: str = "json",
        backend: Optional[str] = None,
    ):
        self.select = select
        self.where = where
        self.limit = int(limit or 0)
        self.input_format = input_format
        self.kernels = get_kernels(backend)
        self.stats = {"rows_scanned": 0, "rows_kernel": 0,
                      "rows_fallback": 0, "bytes_scanned": 0}
        self._fields = sorted(set(_leaf_fields(where, [])))
        self._index = {f: i for i, f in enumerate(self._fields)}
        # select-list columns need spans for projection but no kernel mats
        self._proj_fields = (
            list(dict.fromkeys(select))
            if select and select != ["*"] else None
        )
        if self._fields and input_format == "csv":
            xp = self.kernels.xp
            node = _build_node(where, self._index, self.kernels)

            def tree(pad, fss, lens, press, widths):
                # field gather fused into the kernel: on jax the byte
                # matrices never materialize host-side (widths static)
                env = [
                    (pad[fs[:, None] + xp.arange(w, dtype=fs.dtype)[None, :]],
                     fl, pr)
                    for fs, fl, pr, w in zip(fss, lens, press, widths)
                ]
                return node(env, lens[0].shape[0])

            self._eval = self.kernels.compile(tree, static_argnums=(4,))
        else:
            self._eval = None

    # -- public API ---------------------------------------------------------

    def execute(self, data: bytes) -> list[dict]:
        """Byte-identical to engine.run_query(data, ...) for this plan."""
        out: list[dict] = []
        for batch in self.scan_iter(iter((data,))):
            out.extend(batch)
        return out

    def scan_iter(self, chunks: Iterable[bytes]) -> Iterator[list[dict]]:
        """Streaming core: consume byte chunks (any split points), yield
        batches of matched+projected rows.  Stops consuming as soon as
        the LIMIT is reached, so a prefetching producer gets closed
        early instead of staging the whole object."""
        self.stats = {"rows_scanned": 0, "rows_kernel": 0,
                      "rows_fallback": 0, "bytes_scanned": 0}
        QUERY_COUNTERS["scans"].inc(backend=self.kernels.name)
        if self.input_format == "csv":
            yield from self._scan_csv(chunks)
        else:
            yield from self._scan_json(chunks)

    # -- CSV ----------------------------------------------------------------

    def _scan_csv(self, chunks) -> Iterator[list[dict]]:
        emitted = 0
        header: Optional[list] = None
        carry = b""
        exact_tail: list[bytes] = []  # doc-mode remainder (quotes / \r)
        done = False

        def room() -> int:
            return (self.limit - emitted) if self.limit else -1

        for chunk in chunks:
            self._count_bytes(len(chunk))
            if exact_tail:
                exact_tail.append(chunk)
                continue
            data = carry + chunk if carry else chunk
            cut = data.rfind(b"\n")
            if cut < 0:
                carry = data
                continue
            seg, carry = data[: cut + 1], data[cut + 1 :]
            header, rows, tail = self._csv_segment(seg, header, room())
            if rows:
                emitted += len(rows)
                yield rows
                if self.limit and emitted >= self.limit:
                    # break now, not at the top of the next iteration:
                    # the for-loop would pull (and discard) one more chunk
                    # from the source, skewing upstream byte counters
                    done = True
                    break
            if tail is not None:
                exact_tail.append(tail)
                if carry:
                    # keep byte order: the unterminated carry precedes
                    # any chunks appended on later iterations
                    exact_tail.append(carry)
                    carry = b""
        if done:
            return
        if exact_tail:
            exact_tail.append(carry)
            rows = self._csv_exact(b"".join(exact_tail), header, room())
            if rows:
                yield rows
            return
        if carry:
            # final unterminated line
            header, rows, tail = self._csv_segment(carry, header, room())
            if tail is not None:
                rows = rows + self._csv_exact(tail, header, room() - len(rows)
                                              if self.limit else -1)
            if rows:
                yield rows

    def _count_bytes(self, n: int) -> None:
        self.stats["bytes_scanned"] += n
        QUERY_COUNTERS["bytes"].inc(n)

    def _count_rows(self, kernel: int, fallback: int) -> None:
        self.stats["rows_scanned"] += kernel + fallback
        self.stats["rows_kernel"] += kernel
        self.stats["rows_fallback"] += fallback
        if kernel:
            QUERY_COUNTERS["rows"].inc(kernel)
            QUERY_COUNTERS["kernel"].inc(kernel)
        if fallback:
            QUERY_COUNTERS["rows"].inc(fallback)
            QUERY_COUNTERS["fallback"].inc(fallback)

    def _csv_segment(self, seg, header, room):
        """Vectorized scan of one run of complete lines.  Returns
        (header, matched_rows, exact_tail_bytes_or_None); the tail is
        everything from the first line containing a quote or CR onward —
        bytes the newline index cannot be trusted for.  Byte accounting
        happens once per incoming chunk in _scan_csv, not here."""
        tail = None
        q1, q2 = seg.find(b'"'), seg.find(b"\r")
        q = min(x for x in (q1, q2) if x >= 0) if max(q1, q2) >= 0 else -1
        if q >= 0:
            ls = seg.rfind(b"\n", 0, q) + 1
            seg, tail = seg[:ls], seg[ls:]
        consumed = 0
        if header is None and seg:
            nl = seg.find(b"\n")
            first = seg if nl < 0 else seg[:nl]
            consumed = len(seg) if nl < 0 else nl + 1
            got = list(csv.reader([first.decode("utf-8", errors="replace")]))
            header = got[0] if got else []
        if header is None:
            # no complete line yet and a quote in the header region
            return header, [], tail
        body = seg[consumed:]
        rows: list[dict] = []
        if body:
            arr = np.frombuffer(body, np.uint8)
            if self._eval is not None:
                # pad once (pow2 for jit backends) so field gathers need
                # no per-batch clamping: any in-bounds span plus the
                # width overhang lands in the pad.  Only the overhang
                # window needs zeroing — every kernel read past a
                # field's length is masked by lens/colmask
                cap = len(arr) + _MAX_FIELD_W + 8
                if self.kernels.pads_batches:
                    cap = _pow2(cap, 1 << 16)
                pad = np.empty(cap, dtype=np.uint8)
                pad[: len(arr)] = arr
                pad[len(arr): len(arr) + _MAX_FIELD_W + 8] = 0
                staged = self.kernels.stage(pad)
            else:
                staged = None
            idt = np.int32 if len(arr) < 2**31 - 2 * _MAX_FIELD_W else np.int64
            nls = np.flatnonzero(arr == 10).astype(idt)
            starts = np.empty(len(nls) + 1, dtype=idt)
            starts[0] = 0
            np.add(nls, 1, out=starts[1:])
            ends = np.empty(len(nls) + 1, dtype=idt)
            ends[: len(nls)] = nls
            ends[-1] = len(arr)
            keep = ends > starts  # DictReader skips blank rows
            allkeep = bool(keep.all())
            if self._eval is not None:
                # sentinel commas (== len(arr), pointing at the pad) make
                # out-of-row column indices safe without clamping
                nsent = len(header) + 2
                real = np.flatnonzero(arr == 44)
                commas = np.empty(len(real) + nsent, dtype=idt)
                commas[: len(real)] = real
                commas[len(real):] = len(arr)
                # first-comma index per line, once per segment: the gap
                # between a line's end and the next line's start is just
                # the newline byte, so ci1 is ci0 shifted
                ci0 = np.searchsorted(
                    commas[: len(real)], starts).astype(idt)
                ci1 = np.empty_like(ci0)
                ci1[:-1] = ci0[1:]
                ci1[-1] = len(real)
                if not allkeep:
                    ci0, ci1 = ci0[keep], ci1[keep]
            else:
                commas, ci0, ci1 = None, None, None
            if not allkeep:
                starts, ends = starts[keep], ends[keep]
            for lo in range(0, len(starts), _ROW_BATCH):
                if room >= 0 and len(rows) >= room:
                    break
                hi = min(lo + _ROW_BATCH, len(starts))
                rows.extend(
                    self._csv_batch(
                        body, staged, starts[lo:hi], ends[lo:hi], commas,
                        None if ci0 is None else ci0[lo:hi],
                        None if ci1 is None else ci1[lo:hi], header,
                        -1 if room < 0 else room - len(rows),
                    )
                )
        return header, rows, tail

    def _csv_batch(self, body, staged, starts, ends, commas, ci0, ci1,
                   header, room):
        n = len(starts)
        exact = np.zeros(n, dtype=bool)
        if self._eval is not None:
            ncols = (ci1 - ci0) + 1
            # pow2 row bucket for jit backends: every batch shape recurs,
            # so the tree compiles once per (rows, widths) bucket instead
            # of once per ragged tail
            nb = _pow2(n, 1024) if self.kernels.pads_batches else n
            fss, lens_l, press, widths = [], [], [], []
            for f in self._fields:
                # (start, len, present) of the referenced column under
                # last-dup header semantics (DictReader dict(zip(...)) +
                # restval fill).  Non-present rows keep garbage-but-in-
                # pad starts and length 0; kernels mask by both.
                if "." in f or f not in header:
                    fs = np.zeros(n, dtype=starts.dtype)
                    fl = fs
                    present = np.zeros(n, dtype=bool)
                else:
                    c = len(header) - 1 - header[::-1].index(f)
                    present = c < ncols
                    fs = starts if c == 0 else commas[ci0 + c - 1] + 1
                    fe = np.where(c < ncols - 1, commas[ci0 + c], ends)
                    fl = np.where(present, fe - fs, 0)
                    too_long = fl > _MAX_FIELD_W
                    if too_long.any():
                        exact |= too_long & present
                        fl = np.where(too_long, 0, fl)
                        present = present & ~too_long
                if nb != n:
                    fs = np.concatenate(
                        (fs, np.zeros(nb - n, dtype=fs.dtype)))
                    fl = np.concatenate(
                        (fl, np.zeros(nb - n, dtype=fl.dtype)))
                    present = np.concatenate(
                        (present, np.zeros(nb - n, dtype=bool)))
                fss.append(fs)
                lens_l.append(np.asarray(fl, dtype=np.int32))
                press.append(present)
                widths.append(
                    _pow2(min(int(fl.max()) if n else 1, _MAX_FIELD_W) or 1)
                )
            match, valid = self._eval(staged, fss, lens_l, press,
                                      tuple(widths))
            match = np.asarray(self.kernels.to_host(match), dtype=bool)[:n]
            valid = np.asarray(self.kernels.to_host(valid), dtype=bool)[:n]
            sel = match & valid & ~exact
            exact |= ~valid
        elif self.where:
            # filter references no fields at all ({"and": []} …): its
            # value is document-independent
            sel = np.full(n, _engine._matches({}, self.where))
        else:
            sel = np.ones(n, dtype=bool)

        need_exact = np.flatnonzero(exact)
        if len(need_exact):
            sel = sel.copy()
            for i in need_exact:
                doc = self._csv_doc(body, int(starts[i]), int(ends[i]), header)
                sel[i] = _engine._matches(doc, self.where)
        self._count_rows(n - len(need_exact), len(need_exact))

        proj_cols = None
        if self._proj_fields is not None:
            proj_cols = [
                (f,
                 len(header) - 1 - header[::-1].index(f)
                 if "." not in f and f in header else -1)
                for f in self._proj_fields
            ]
        out = []
        for i in np.flatnonzero(sel):
            if room >= 0 and len(out) >= room:
                break
            if proj_cols is not None:
                fields = body[int(starts[i]): int(ends[i])].decode(
                    "utf-8", errors="replace").split(",")
                # value = col if the row reaches the column's LAST dup
                # index, else None — exactly DictReader's zip + restval
                # overwrite behavior
                out.append({
                    f: fields[c] if 0 <= c < len(fields) else None
                    for f, c in proj_cols
                })
            else:
                out.append(
                    self._csv_doc(body, int(starts[i]), int(ends[i]), header)
                )
        return out

    @staticmethod
    def _csv_doc(body, s, e, header):
        """Replicate DictReader's dict building for one quote-free line
        (restkey None for long rows, restval None fill for short — and
        the fill OVERWRITES duplicated trailing names, same as the
        stdlib)."""
        fields = body[s:e].decode("utf-8", errors="replace").split(",")
        d = dict(zip(header, fields))
        lf, lr = len(header), len(fields)
        if lf < lr:
            d[None] = fields[lf:]
        elif lf > lr:
            for key in header[lr:]:
                d[key] = None
        return d

    def _csv_exact(self, data, header, room) -> list[dict]:
        """Exact lane for quoted / CR-bearing regions: the stdlib csv
        parser resumed at a record boundary with the header captured by
        the vectorized prefix."""
        text = data.decode("utf-8", errors="replace")
        if header is None:
            reader = csv.DictReader(io.StringIO(text))
        else:
            reader = csv.DictReader(io.StringIO(text), fieldnames=header)
        out = []
        nrows = 0
        for doc in reader:
            nrows += 1
            if _engine._matches(doc, self.where):
                out.append(_engine._project(doc, self.select))
                if room >= 0 and len(out) >= room:
                    break
        self._count_rows(0, nrows)
        return out

    # -- JSON ---------------------------------------------------------------

    def _scan_json(self, chunks) -> Iterator[list[dict]]:
        """JSON-lines stream through the exact lane (structural newline
        segmentation is the only vectorizable part); a JSON array
        document buffers and degenerates to the engine."""
        emitted = 0
        carry = b""
        mode = None  # None → undecided, "lines", "doc"
        doc_buf: list[bytes] = []
        for chunk in chunks:
            self._count_bytes(len(chunk))
            if mode == "doc":
                doc_buf.append(chunk)
                continue
            carry += chunk
            if mode is None:
                probe = carry.decode("utf-8", errors="replace").lstrip()
                if not probe:
                    continue  # pure whitespace so far; keep buffering
                mode = "doc" if probe.startswith("[") else "lines"
                if mode == "doc":
                    doc_buf.append(carry)
                    carry = b""
                    continue
            cut = carry.rfind(b"\n")
            if cut < 0:
                continue
            seg, carry = carry[: cut + 1], carry[cut + 1 :]
            rows, emitted = self._json_lines(seg, emitted)
            if rows:
                yield rows
            if self.limit and emitted >= self.limit:
                return
        if mode == "doc":
            data = b"".join(doc_buf)
            docs = list(_engine._iter_docs(data, "json"))
            self._count_rows(0, len(docs))
            out = []
            for doc in docs:
                if _engine._matches(doc, self.where):
                    out.append(_engine._project(doc, self.select))
                    if self.limit and len(out) >= self.limit:
                        break
            if out:
                yield out
        elif carry:
            rows, emitted = self._json_lines(carry, emitted)
            if rows:
                yield rows

    def _json_lines(self, seg: bytes, emitted: int):
        out = []
        nrows = 0
        for line in seg.decode("utf-8", errors="replace").splitlines():
            line = line.strip()
            if not line:
                continue
            doc = json.loads(line)
            nrows += 1
            if _engine._matches(doc, self.where):
                out.append(_engine._project(doc, self.select))
                emitted += 1
                if self.limit and emitted >= self.limit:
                    break
        self._count_rows(0, nrows)
        return out, emitted


def compile_plan(
    select: Optional[list] = None,
    where: Optional[dict] = None,
    limit: int = 0,
    input_format: str = "json",
    backend: Optional[str] = None,
) -> ScanPlan:
    return ScanPlan(select, where, limit, input_format, backend)


def run_scan(
    data: bytes,
    input_format: str = "json",
    select: Optional[list] = None,
    where: Optional[dict] = None,
    limit: int = 0,
    backend: Optional[str] = None,
) -> list[dict]:
    """Drop-in vectorized twin of engine.run_query."""
    return compile_plan(select, where, limit, input_format, backend).execute(
        data
    )
