"""Device-resident mirror of the cross-tick score cache
(docs/performance.md, "Device-resident scoring").

``ScoreCache`` already makes the numpy hot path sublinear per tick; a
device scorer that re-shipped the full ``[J, W]`` matrices from host to
device on every tick would lose that at the host/device boundary
instead of in the scoring math (the PerLLM, arXiv:2405.14636,
per-decision-overhead argument).

``DeviceScoreCache`` keeps the host ``ScoreCache`` as the row oracle
(row *values* are host computations over the Configuration Dictionary)
and mirrors every written row into float32 device pools that persist
across ticks, applying the same invalidation rules incrementally
on-device:

* **arrivals** append: the newly inserted rows ship as one batched
  scatter (``pool.at[idx].set(rows)`` under a donated jit, so the pool
  buffer is updated in place) — O(churn * W) bytes;
* **placements / finishes** reclaim lazily exactly like the host cache:
  a departed row simply stops being gathered (validity is the slot
  vector itself), zero device traffic;
* **elastic clones** extend the worker axis in padded column blocks:
  the old block moves device-to-device, only the new columns of live
  rows are uploaded;
* **failure generations mask instead of re-uploading**: the host cache
  flushes on any ``fail_gen`` bump out of pure conservatism — failure
  state never enters the Eq. 2 rows — so the device mirror adopts the
  new generation and keeps every resident row (recomputing them would
  reproduce the same bits).  ``profile_gen`` bumps reclaim exactly the
  refreshed engines' slots (the PR 7 rule), so only those rows re-ship;
  non-append membership changes genuinely change the row shape and
  still flush.

``device_tick`` then runs the whole decision — row gather by slot
index, the fused Eq. 2-4 scoring kernel, and the urgency-ordered greedy
placement — as one ``repro.kernels.scheduler_score.scheduler_tick``
dispatch; the host ships only O(J + W) per-tick vectors and receives
the (job, worker) assignment indices.  Parity with the cached numpy
path and the O(churn * W) transfer bound are pinned by
``tests/test_devicecache.py``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.estimator import profile_gen
from repro.core.scorecache import ScoreCache
from repro.kernels.scheduler_score import resolve_interpret, scheduler_tick

_COL_BLOCK = 128      # worker-axis padding block (f32 TPU lane width)
_J_BLOCK = 128        # tick-kernel job block (rows per grid step)
_ROW_BLOCK = 256      # slot-pool row padding block (matches _GROW)
_UP_BLOCK = 8         # upload-batch padding block (recompile guard)


def _bucket(n: int, block: int) -> int:
    """Smallest power-of-two multiple of ``block`` >= n — shapes stay in
    a tiny set so the jitted upload/tick dispatches never recompile in
    steady state."""
    b = block
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(pool, idx, rows):
    return pool.at[idx].set(rows)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("start", "width"))
def _scatter_cols(pool, idx, block, *, start, width):
    return pool.at[idx, start:start + width].set(block)


class DeviceScoreCache(ScoreCache):
    """A ``ScoreCache`` whose Eq. 2 rows are additionally resident on
    the jax device, plus the fused one-dispatch tick entry point."""

    def __init__(self, use_default: bool = False, profile: int = 0):
        # device pools (created lazily on first upload)
        self._dt = self._dpre = self._ddec = self._dene = None
        self._d_cap = 0
        self._d_Wp = 0
        super().__init__(use_default, profile)
        # compiled tick on a TPU backend, the Pallas interpreter elsewhere
        self.interpret = resolve_interpret()
        # transfer accounting (tests assert the O(churn * W) bound)
        self.fail_masks = 0          # fail_gen bumps absorbed by masking
        self.rows_uploaded = 0       # matrix rows shipped host -> device
        self.bytes_to_device = 0     # every host -> device payload byte
        self.ticks = 0

    # ------------------------------------------------------------------
    # invalidation overrides

    def sync(self, cd, queue, cluster) -> np.ndarray:
        key = (cluster.serial, cluster.worker_token, cluster.fail_gen,
               profile_gen(cd, self.profile))
        old = self._key
        if (old is not None and key != old and old[0] == key[0]
                and old[1] == key[1] and old[3] == key[3]):
            # pure failure-generation bump: the host rule flushes out of
            # conservatism, but failure state never enters the rows —
            # same cluster, same worker tuple, same profile means a
            # recompute would reproduce every row bit-for-bit.  Mask:
            # adopt the new generation, keep host + device rows.
            self._key = key
            self.fail_masks += 1
        return super().sync(cd, queue, cluster)

    def _flush(self, W: int):
        super()._flush(W)
        self._dt = self._dpre = self._ddec = self._dene = None
        self._d_cap = 0
        self._d_Wp = 0

    def _insert(self, jobs, cd, cluster, slots, miss):
        super()._insert(jobs, cd, cluster, slots, miss)
        self._upload_rows(np.asarray(slots[miss], dtype=np.int64))

    def _extend_columns(self, cd, queue, cluster, names):
        old_W = self._W
        super()._extend_columns(cd, queue, cluster, names)
        if self._dt is None:
            return
        # widen the pools (old block moves device-to-device), then ship
        # only the new columns of the live rows
        Wp = _bucket(self._W, _COL_BLOCK)
        if Wp > self._d_Wp:
            self._d_Wp = Wp
            regrow = lambda p: (None if p is None else
                                jnp.full((self._d_cap, Wp), jnp.inf,
                                         jnp.float32)
                                .at[:, :p.shape[1]].set(p))
            self._dt = regrow(self._dt)
            self._dpre = regrow(self._dpre)
            self._ddec = regrow(self._ddec)
            self._dene = regrow(self._dene)
        sl = np.fromiter(self._slot.values(), np.int64, len(self._slot))
        n, width = len(sl), self._W - old_W
        if not n or not width:
            return
        nb = _bucket(n, _UP_BLOCK)
        idx = np.empty(nb, np.int32)
        idx[:n] = sl
        idx[n:] = sl[-1]

        def ship_cols(pool, host):
            block = np.empty((nb, width), np.float32)
            block[:n] = host[sl, old_W:self._W]
            block[n:] = block[n - 1]
            self.bytes_to_device += block.nbytes + idx.nbytes
            return _scatter_cols(pool, jnp.asarray(idx),
                                 jnp.asarray(block), start=old_W,
                                 width=width)

        self._dt = ship_cols(self._dt, self._t)
        if self._have_phase:
            self._dpre = ship_cols(self._dpre, self._pre)
            self._ddec = ship_cols(self._ddec, self._dec)
        if self._have_energy:
            self._dene = ship_cols(self._dene, self._ene)

    def ensure_phase_rows(self, cd, queue, slots, cluster):
        fresh = not self._have_phase
        super().ensure_phase_rows(cd, queue, slots, cluster)
        if fresh and len(queue):
            # one-time materialization: ship the live prefill/decode
            # rows; later inserts keep them current
            live = np.fromiter(self._slot.values(), np.int64,
                               len(self._slot))
            self._upload_rows(live, which=("pre", "dec"))

    def ensure_energy_rows(self, cd, queue, slots, cluster):
        fresh = not self._have_energy
        super().ensure_energy_rows(cd, queue, slots, cluster)
        if fresh and len(queue):
            live = np.fromiter(self._slot.values(), np.int64,
                               len(self._slot))
            self._upload_rows(live, which=("ene",))

    # ------------------------------------------------------------------
    # device pool maintenance

    def _ensure_pools(self):
        """Size every active pool to (padded cap, padded W); freshly
        exposed regions hold inf and are only ever read after an upload
        writes them (stale slots are never gathered)."""
        cap = max(self._d_cap, _bucket(max(self._cap, 1), _ROW_BLOCK))
        Wp = max(self._d_Wp, _bucket(max(self._W, 1), _COL_BLOCK))

        def fit(p):
            if p is not None and p.shape == (cap, Wp):
                return p
            fresh = jnp.full((cap, Wp), jnp.inf, jnp.float32)
            if p is None:
                return fresh
            return fresh.at[:p.shape[0], :p.shape[1]].set(p)

        self._dt = fit(self._dt)
        if self._have_phase:
            self._dpre = fit(self._dpre)
            self._ddec = fit(self._ddec)
        if self._have_energy:
            self._dene = fit(self._dene)
        self._d_cap, self._d_Wp = cap, Wp

    def _upload_rows(self, dest: np.ndarray, which=("t", "pre", "dec",
                                                    "ene")):
        """Batched dynamic-update-slice of freshly written host rows into
        the device pools: O(rows * W) bytes, the only matrix traffic a
        steady-state tick pays."""
        n = len(dest)
        if not n:
            return
        self._ensure_pools()
        Wp = self._d_Wp
        nb = _bucket(n, _UP_BLOCK)
        idx = np.empty(nb, np.int32)
        idx[:n] = dest
        idx[n:] = dest[-1]      # padding re-writes the last row's values
        jidx = jnp.asarray(idx)
        self.bytes_to_device += idx.nbytes

        def ship(pool, host):
            rows = np.full((nb, Wp), np.inf, np.float32)
            rows[:n, :self._W] = host[dest]
            rows[n:] = rows[n - 1]
            self.bytes_to_device += rows.nbytes
            return _scatter_rows(pool, jidx, jnp.asarray(rows))

        if "t" in which:
            self._dt = ship(self._dt, self._t)
        if self._have_phase and "pre" in which:
            self._dpre = ship(self._dpre, self._pre)
        if self._have_phase and "dec" in which:
            self._ddec = ship(self._ddec, self._dec)
        if self._have_energy and "ene" in which:
            self._dene = ship(self._dene, self._ene)
        if "t" in which:
            self.rows_uploaded += n

    # ------------------------------------------------------------------
    # the fused one-dispatch tick

    def device_tick(self, slots, t_rem, ttft_rem, tpot_qos, dtok,
                    has_ttft, has_tpot, phase, ekey, emask, pen,
                    busy_wait, avail, escale=None):
        """Run one whole scheduling decision on-device.  All inputs are
        host vectors over the live queue ([J]) or the fleet ([W] /
        [K, W]); Eq. 1 decay (t_rem, ttft_rem) is computed on host in
        float64 from the cached scalars — an O(J) vector op, cast to f32
        for the kernel.  Returns (assign [Jp], order [Jp]) as numpy
        int32."""
        self._ensure_pools()
        J, W = len(slots), self._W
        Wp = self._d_Wp
        Jp = _bucket(max(J, 1), _J_BLOCK)
        use_energy = escale is not None

        def padj(a, fill, dt):
            out = np.full(Jp, fill, dt)
            out[:J] = a
            return out

        def padw(a, fill, dt):
            out = np.full(Wp, fill, dt)
            out[:W] = a
            return out

        slots_p = padj(slots, -1, np.int32)
        K = emask.shape[0]
        Kp = _bucket(K, 1)
        em = np.zeros((Kp, Wp), bool)
        em[:K, :W] = emask
        args = (slots_p,
                padj(t_rem, -1.0, np.float32),
                padj(ttft_rem, -1.0, np.float32),
                padj(tpot_qos, 1.0, np.float32),
                padj(dtok, 1.0, np.float32),
                padj(has_ttft, 0, np.int32),
                padj(has_tpot, 0, np.int32),
                padj(phase, 0, np.int32),
                padj(ekey, 0, np.int32),
                em,
                padw(pen, 1.0, np.float32),
                padw(busy_wait, 0.0, np.float32),
                padw(escale if use_energy else np.zeros(W), 0.0,
                     np.float32),
                padw(avail, False, bool))
        self.bytes_to_device += sum(a.nbytes for a in args)
        self.ticks += 1
        pool_pre = self._dpre if self._have_phase else self._dt
        pool_dec = self._ddec if self._have_phase else self._dt
        pool_ene = (self._dene if use_energy
                    else jnp.zeros((1, Wp), jnp.float32))
        assign, order = scheduler_tick(
            self._dt, pool_pre, pool_dec, pool_ene,
            *(jnp.asarray(a) for a in args),
            use_energy=use_energy, bj=_J_BLOCK,
            interpret=self.interpret)
        return np.asarray(assign), np.asarray(order)
