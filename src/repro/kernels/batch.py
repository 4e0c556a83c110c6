"""Device-offloaded aggregation: the batching layer between the phase-2
streaming engine and the Pallas kernels (ROADMAP item 3).

The idiom is MaxText-offline-inference-shaped: requests are coalesced and
padded into a small set of **shape classes** (power-of-two column buckets),
so many profiles' fused-transform work becomes one device launch per batch
instead of one per profile, and the jit cache stays bounded no matter how
many distinct profile shapes stream through.  Three hot loops route here:

* **inclusive propagation** — the O(n_ctx x m) cumsum of the fused kernel
  becomes a batched :func:`repro.kernels.ops.inclusive_from_exclusive`
  launch: all profiles share the unified tree's preorder length ``n``, so
  their exclusive matrices sit side by side in columns of one
  ``(n, M_total)`` blockscan.  A profile ships only its non-zeros, as
  (row, column, value) triplets, and the (row, column) pairs it wants
  back; the device builds the matrix and returns the sums at those pairs.
  Prefix sums are column-independent, so a profile's result is a pure
  function of its own columns — **batch composition cannot perturb
  bytes**, which is what keeps the device path deterministic across
  executors and shard counts.
* **duplicate-key combine** — the stable-sorted segment sums behind
  :func:`repro.core.pipeline._combine_sorted` dispatch to the ``segstats``
  one-hot MXU kernel.  These launch per-profile (never concatenated:
  moving value-block boundaries would change f32 summation order with
  batch composition), with sizes padded to power-of-two buckets.
* **CMS stripe offsets / census** — the §4.3.2 exclusive scan runs through
  ``ops.exclusive_scan`` on int32 (exact, so CMS bytes never change), and
  the census histogram through ``ops.histogram`` on real accelerators.

Per-profile summary *statistics* do not offload: after the combine, each
profile's (ctx, mid) keys are unique, so the per-profile "stats" are the
identity (v, 1, v, v, v^2) — the real reduction is the cross-profile merge,
which :class:`repro.runtime.reduce.AsyncStreamingReducer` moves off the
consume thread instead.

Dtype contract (asserted per-plane by tests/test_pipeline.py): device
accumulation is f32.  A plane classifies as **"exact"** when every value is
an integer and both ``sum(|v|)`` and ``sum(v^2)`` stay within 2^24 — then
every partial sum is exactly representable in f32 regardless of
association order and device output is byte-identical to the CPU f64 path.
Anything else is **"f32"**: device values carry f32 rounding (and near-zero
inclusive sums may round to exactly 0.0 and drop out of the sparse plane).
The class is a pure function of the plane, never of the executor or batch,
so either way all backends agree byte-for-byte *with each other*.

Threading: the cross-thread coalescer is a combining funnel — no timers,
no dedicated dispatch thread.  A requester that finds no launch in flight
becomes the launcher and drains the pending list until it is empty; all
other requesters park on an event.  Device dispatch releases the GIL, which
is precisely what rescues the ``threads`` executor (its argsort-bound 1.56x
vs 1.91x-serial deficit, ROADMAP item 3).
"""
from __future__ import annotations

import threading

import numpy as np

from repro.core.timer import PhaseTimer

LANE = 128     # minor-dim tile multiple (f32)
SUBLANE = 8    # second-minor tile multiple (f32)

# below this many values the CPU bincount beats a kernel launch even on a
# real accelerator; a constant, so the offload decision is a pure function
# of the plane (executor/batch independent)
DEVICE_COMBINE_MIN = 4096

# floors of the sparse propagation's shape classes: one launch of up to
# four PeleC(1+82) profiles (<= 748 values, <= 1,114 support pairs each)
# fits one triplet class and one support class at every column class
TRIPLET_FLOOR = 4096
SUPPORT_FLOOR = 8192

# f32 integer-exactness ceiling: 2^24 (see module docstring)
_EXACT_LIMIT = 2.0 ** 24


def has_accelerator() -> bool:
    """JAX's default backend is an accelerator (TPU/GPU), not the CPU."""
    import jax
    return jax.default_backend() != "cpu"


def classify_plane(vals) -> str:
    """The per-plane dtype contract: ``"exact"`` or ``"f32"`` (docstring
    above).  Pure function of the values — every executor, shard count and
    batch composition classifies a given plane identically."""
    v = np.asarray(vals, dtype=np.float64)
    if v.size == 0:
        return "exact"
    if not np.all(np.isfinite(v)) or np.any(v != np.rint(v)):
        return "f32"
    a = np.abs(v)
    if a.sum() > _EXACT_LIMIT or np.sum(a * a) > _EXACT_LIMIT:
        return "f32"
    return "exact"


def _bucket(x: int, floor: int) -> int:
    """Next power-of-two >= max(x, floor): the shape-class ladder that keeps
    jit recompiles O(log(max size)) instead of O(distinct sizes)."""
    b = int(floor)
    x = int(x)
    while b < x:
        b *= 2
    return b


class _Request:
    __slots__ = ("out", "err", "event")

    def __init__(self):
        self.out: np.ndarray | None = None
        self.err: BaseException | None = None
        self.event = threading.Event()


class _DenseRequest(_Request):
    """A dense (n, m) exclusive matrix; its (n, m) inclusive sums back."""
    __slots__ = ("cols",)

    def __init__(self, cols: np.ndarray):
        super().__init__()
        self.cols = cols


class _SparseRequest(_Request):
    """The non-zeros of an (n, width) exclusive matrix as ``rows``,
    ``cols`` and ``vals``; its inclusive sums back at the pairs
    ``(ir, ic)`` only."""
    __slots__ = ("width", "rows", "cols", "vals", "ir", "ic")

    def __init__(self, width, rows, cols, vals, ir, ic):
        super().__init__()
        self.width = int(width)
        self.rows, self.cols, self.vals = rows, cols, vals
        self.ir, self.ic = ir, ic


class DeviceAggregator:
    """Per-run device context: the unified tree's ``end`` array resident on
    device, the power-of-two shape-class jit cache, and the combining
    funnel that coalesces concurrent threads' inclusive-propagation work
    into single launches.

    One instance serves one phase-2 run, shared by all worker threads of
    the one process that holds the device.  ``timer`` (the run's
    :class:`repro.core.timer.PhaseTimer`) receives the ``device/*`` spans of
    the propagation launches and the transfer and lane counters.
    """

    def __init__(self, end: np.ndarray, *, offload_combine: bool | None = None,
                 combine_min: int = DEVICE_COMBINE_MIN,
                 timer: PhaseTimer | None = None):
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        self._jax = jax
        self._jnp = jnp
        self._ops = ops
        self.timer = timer if timer is not None else PhaseTimer()
        end = np.ascontiguousarray(np.asarray(end, dtype=np.int64))
        if end.size and int(end.max()) > np.iinfo(np.int32).max:
            raise ValueError("unified tree too large for int32 device ids")
        self.n = int(end.size)
        end32 = end.astype(np.int32)
        with self.timer.span("device/h2d"):
            self._end_dev = jnp.asarray(end32)
        self.timer.add("device_h2d_bytes", end32.nbytes)
        self._incl_fn = jax.jit(ops.inclusive_from_exclusive,
                                static_argnames="columns")
        self.interpret = not has_accelerator()
        # the one-hot combine is MXU free-lunch on hardware but O(n*S) host
        # work under the interpret proxy, so it defaults off there; tests
        # force it on tiny planes to validate the wiring
        self.offload_combine = (not self.interpret if offload_combine is None
                                else bool(offload_combine))
        self.combine_min = int(combine_min)

        self._lock = threading.Lock()
        self._pending: list[_Request] = []
        self._launching = False
        # observability (reported through AnalysisResult.timings)
        self.inclusive_launches = 0
        self.combine_launches = 0
        self.requests = 0

    @property
    def launches(self) -> int:
        return self.inclusive_launches + self.combine_launches

    # -- inclusive propagation (the batched hot loop) ------------------------

    def inclusive(self, cols: np.ndarray) -> np.ndarray:
        """``out[i, c] = sum(cols[i:end[i], c])`` for each column — the
        preorder-interval inclusive sums, f32.  Thread-safe; concurrent
        callers' columns ride one launch."""
        return self._submit(
            _DenseRequest(np.ascontiguousarray(cols, dtype=np.float32)))

    def inclusive_at(self, rows: np.ndarray, cols: np.ndarray,
                     vals: np.ndarray, width: int, ir: np.ndarray,
                     ic: np.ndarray) -> np.ndarray:
        """:meth:`inclusive` of the (n, ``width``) matrix whose non-zeros
        are ``vals`` at ``(rows, cols)`` (unique pairs), read at the pairs
        ``(ir, ic)`` only: one f32 value a pair.  Only the triplets and the
        pairs cross the link, as int32 and f32; the device builds the
        matrix, at the shape and with the bits the host would have built
        it.  Thread-safe; concurrent callers ride one launch."""
        return self._submit(_SparseRequest(width, rows, cols, vals, ir, ic))

    def _submit(self, req: _Request) -> np.ndarray:
        with self._lock:
            self._pending.append(req)
            self.requests += 1
            i_launch = not self._launching
            if i_launch:
                self._launching = True
        if i_launch:
            while True:
                with self._lock:
                    batch = self._pending
                    self._pending = []
                    if not batch:
                        self._launching = False
                        break
                self._launch(batch)
        with self.timer.span("device/wait"):  # on another thread's launch
            req.event.wait()
        if req.err is not None:
            raise req.err
        return req.out

    def _launch(self, batch: list[_Request]) -> None:
        try:
            for kind, launch in ((_DenseRequest, self._launch_dense),
                                 (_SparseRequest, self._launch_sparse)):
                requests = [r for r in batch if isinstance(r, kind)]
                if requests:
                    launch(requests)
                    self.inclusive_launches += 1
        except BaseException as e:
            for r in batch:
                r.err = e
        finally:
            for r in batch:
                r.event.set()

    def _launch_dense(self, batch: list[_DenseRequest]) -> None:
        widths = [r.cols.shape[1] for r in batch]
        with self.timer.span("device/pack"):
            mat = (batch[0].cols if len(batch) == 1
                   else np.concatenate([r.cols for r in batch], axis=1))
        out = self._inclusive_padded(mat)
        o = 0
        for r, w in zip(batch, widths):
            r.out = out[:, o:o + w]
            o += w

    def _inclusive_padded(self, mat: np.ndarray) -> np.ndarray:
        timer = self.timer
        n, m = mat.shape
        mb = _bucket(m, SUBLANE)
        if mb != m:  # zero columns: cumsum is column-local, results unchanged
            with timer.span("device/pack"):
                mat = np.concatenate(
                    [mat, np.zeros((n, mb - m), dtype=np.float32)], axis=1)
        # the copy to the device returns once JAX holds the matrix; the
        # kernel's span waits for the rest of the copy and the kernel
        with timer.span("device/h2d"):
            x = self._jnp.asarray(mat)
        with timer.span("device/kernel"):
            y = self._incl_fn(x, self._end_dev).block_until_ready()
        with timer.span("device/d2h"):
            out = np.asarray(y)
        timer.add("device_h2d_bytes", mat.nbytes)
        timer.add("device_d2h_bytes", out.nbytes)
        timer.add("device_columns", m)
        timer.add("device_padded_columns", mb)
        return out[:, :m]

    def _launch_sparse(self, batch: list[_SparseRequest]) -> None:
        """One launch of every request's triplets, side by side in columns
        as :meth:`_launch_dense` concatenates matrices: request k's column
        indices move by the widths of the requests before it.  Each array
        is padded to its power-of-two class; a padding triplet points past
        the last row and is dropped, a padding pair reads (0, 0) and is
        discarded."""
        timer = self.timer
        with timer.span("device/pack"):
            col0 = np.cumsum([0] + [r.width for r in batch])
            nt = sum(r.rows.size for r in batch)
            ns = sum(r.ir.size for r in batch)
            m = int(col0[-1])
            mb = _bucket(m, SUBLANE)
            tb = _bucket(nt, TRIPLET_FLOOR)
            sb = _bucket(ns, SUPPORT_FLOOR)
            rows = np.full(tb, self.n, dtype=np.int32)
            cols = np.zeros(tb, dtype=np.int32)
            vals = np.zeros(tb, dtype=np.float32)
            ir = np.zeros(sb, dtype=np.int32)
            ic = np.zeros(sb, dtype=np.int32)
            t = s = 0
            for r, c0 in zip(batch, col0):
                k = r.rows.size
                rows[t:t + k], vals[t:t + k] = r.rows, r.vals
                cols[t:t + k] = r.cols + c0
                t += k
                k = r.ir.size
                ir[s:s + k] = r.ir
                ic[s:s + k] = r.ic + c0
                s += k
        with timer.span("device/h2d"):
            x = self._jax.device_put((rows, cols, vals, ir, ic))
        with timer.span("device/kernel"):
            y = self._incl_fn(x[:3], self._end_dev, x[3:],
                              columns=mb).block_until_ready()
        with timer.span("device/d2h"):
            out = np.asarray(y)
        timer.add("device_h2d_bytes", sum(a.nbytes for a in
                                          (rows, cols, vals, ir, ic)))
        timer.add("device_d2h_bytes", out.nbytes)
        timer.add("device_values_in", nt)
        timer.add("device_values_out", ns)
        timer.add("device_columns", m)
        timer.add("device_padded_columns", mb)
        s = 0
        for r in batch:
            r.out = out[s:s + r.ir.size]
            s += r.ir.size

    # -- duplicate-key combine (per-profile segment sums) --------------------

    def wants_combine(self, n_values: int) -> bool:
        return self.offload_combine and n_values >= self.combine_min

    def combine_sums(self, seg_sorted: np.ndarray, vals: np.ndarray
                     ) -> np.ndarray:
        """Segment sums over stable-sorted dense ranks via the ``segstats``
        MXU kernel; f32 accumulation (see the module dtype contract).
        Launches are per-profile with bucket-padded shapes: concatenating
        different profiles' value streams would move block boundaries and
        change f32 summation order with batch composition."""
        x = int(seg_sorted.size)
        n_seg = int(seg_sorted[-1]) + 1 if x else 0
        if n_seg == 0:
            return np.zeros(0, dtype=np.float64)
        sb = _bucket(n_seg, LANE)
        xb = _bucket(x, LANE)
        ids = np.full(xb, sb, dtype=np.int32)  # sentinel: matches no segment
        ids[:x] = seg_sorted
        v = np.zeros(xb, dtype=np.float32)
        v[:x] = vals
        out = self._ops.segstats(self._jnp.asarray(ids),
                                 self._jnp.asarray(v), sb)
        sums = np.asarray(out[:n_seg, 0])
        self.combine_launches += 1
        self.timer.add("device_h2d_bytes", ids.nbytes + v.nbytes)
        self.timer.add("device_d2h_bytes", sums.nbytes)
        return sums.astype(np.float64)


# ---------------------------------------------------------------------------
# CMS helpers (module-level: no per-run state needed)
# ---------------------------------------------------------------------------

def device_offsets(sizes: np.ndarray, timer: PhaseTimer | None = None
                   ) -> np.ndarray | None:
    """CMS stripe offsets by device exclusive scan (paper §4.3.2), int32
    (the container runs without x64; f32 would corrupt offsets > 2^24).
    Integer cumsum is exact, so the result is byte-identical to
    ``np.cumsum`` and CMS output bytes never depend on the backend.
    Returns None (caller falls back to numpy) when the total would
    overflow int32 — a decision that depends only on the sizes, so every
    executor path makes it identically.  ``timer`` counts the bytes each
    way."""
    sizes = np.asarray(sizes, dtype=np.int64)
    if sizes.size == 0 or int(sizes.sum()) >= np.iinfo(np.int32).max:
        return None
    import jax.numpy as jnp

    from repro.kernels import ops
    sizes32 = sizes.astype(np.int32)
    out = np.asarray(ops.exclusive_scan(jnp.asarray(sizes32)))
    _count_transfer(timer, sizes32, out)
    return out.astype(np.int64)


def device_census_counts(rows_all: np.ndarray, n_ctx: int,
                         timer: PhaseTimer | None = None) -> np.ndarray | None:
    """Per-context value counts via the one-hot ``histogram`` kernel — one
    launch over every profile's concatenated rows (unsorted ids are fine
    for scatter_add).  Real accelerators only: the O(values x contexts)
    mask work is MXU throwaway on TPU but a dealbreaker on the interpret
    proxy.  Counts are integers < 2^24 (guarded), so f32 accumulation is
    exact and the result matches ``np.add.at`` byte-for-byte.  ``timer``
    counts the bytes each way."""
    if not has_accelerator() or n_ctx == 0:
        return None
    rows_all = np.asarray(rows_all)
    if rows_all.size >= 1 << 24:  # f32 count-exactness guard
        return None
    import jax.numpy as jnp

    from repro.kernels import ops
    rows32 = rows_all.astype(np.int32)
    counts = np.asarray(ops.histogram(jnp.asarray(rows32), int(n_ctx)))
    _count_transfer(timer, rows32, counts)
    return counts.astype(np.int64)


def _count_transfer(timer: PhaseTimer | None, sent: np.ndarray,
                    received: np.ndarray) -> None:
    if timer is not None:
        timer.add("device_h2d_bytes", sent.nbytes)
        timer.add("device_d2h_bytes", received.nbytes)
