"""Utility-ordered bounded queues with dynamic sizing (paper §IV-D).

Second layer of admission control: when a queue is full, the
lowest-utility frame is evicted (whether resident or incoming); the
transmission layer always sends the current *best* frame. Queues never
shrink below size 1 ("avoid starving the downstream operators").

Two forms of the same contract:

``UtilityQueue``
    The original scalar heapq queue — one Python object per camera.
    Kept as the executable *reference semantics* (the array lanes are
    property-tested against it) and as the single-camera
    ``LoadShedder``'s queue.

Array lanes (the ``*_dev`` functions)
    The serve-path hot form: C cameras' queues as fixed-capacity
    ``(C, K)`` ``util``/``seq`` lanes (empty slots ``util=-inf``,
    ``seq=-1``) plus a ``(C,)`` ``next_seq`` push counter, so queue
    state joins the session's checkpointable pytree and admission is
    pure array code. Each operation is pure jnp, traced into the
    control plane's jitted programs (``repro.core.control_plane``).

    Ordering contract (must match the heapq reference exactly):
      * eviction removes the minimum by ``(utility, seq)`` — lowest
        utility first, FIFO (oldest ``seq``) among ties;
      * ``pop_best`` removes the maximum utility, oldest ``seq`` among
        ties; the any-camera variant prefers the lowest camera index
        among utility ties.
      * a batch of pushes into a bounded queue leaves exactly the
        top-``cap`` of residents ∪ admitted by ``(utility, seq)`` —
        order-free top-k selection is equivalent to sequential
        push/evict because eviction always removes the current minimum
        of a totally ordered set.

    Utilities are assumed finite (the model's scores are); ``-inf`` is
    reserved for empty slots and ``+inf`` for sort sentinels. Every
    push first maps subnormal utilities (and -0.0) to +0.0 with
    :func:`flush_subnormal`, the reference queue included.
"""
from __future__ import annotations

import heapq
import itertools
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INT32_MAX = np.int32(2**31 - 1)
TINY = np.float32(np.finfo(np.float32).tiny)   # smallest normal float32


def flush_subnormal(u, xp=np):
    """float32 utilities with subnormals (and -0.0) mapped to +0.0.

    XLA compares floats with subnormals flushed to zero (CPU and TPU
    alike), so on the device a subnormal ties with 0.0 where NumPy and
    Python order the two strictly. Flushing each utility as it enters a
    queue or a CDF ring gives every implementation the same values."""
    u = xp.asarray(u, xp.float32)
    return xp.where(xp.abs(u) < TINY, xp.float32(0.0), u)


@dataclass(order=True)
class _Entry:
    utility: float
    seq: int                      # FIFO tiebreak: prefer older on eviction? paper
    item: Any = field(compare=False)
    dropped: bool = field(default=False, compare=False)


class UtilityQueue:
    """Min-heap on utility so eviction of the worst frame is O(log n);
    pop_best scans lazily via a parallel max-heap."""

    def __init__(self, max_size: int = 8):
        self._max = max(1, int(max_size))
        self._min: List[_Entry] = []
        self._max_heap: List[Tuple[float, int, _Entry]] = []
        self._counter = itertools.count()
        self.evictions = 0

    def __len__(self):
        return sum(1 for e in self._min if not e.dropped)

    @property
    def max_size(self) -> int:
        return self._max

    def resize(self, new_size: int) -> List[Any]:
        """Dynamic queue sizing: shrink drops the lowest-utility frames."""
        self._max = max(1, int(new_size))
        dropped = []
        while len(self) > self._max:
            dropped.append(self._evict_worst())
        return dropped

    def push(self, item: Any, utility: float) -> Optional[Any]:
        """Insert; returns the evicted item (possibly ``item`` itself) or None."""
        u = float(utility)
        if abs(u) < TINY:           # flush_subnormal, at the input's width
            u = 0.0
        e = _Entry(u, next(self._counter), item)
        heapq.heappush(self._min, e)
        heapq.heappush(self._max_heap, (-e.utility, e.seq, e))
        if len(self) > self._max:
            self.evictions += 1
            return self._evict_worst()
        return None

    def _evict_worst(self) -> Any:
        while self._min:
            e = heapq.heappop(self._min)
            if not e.dropped:
                e.dropped = True
                return e.item
        raise RuntimeError("evict from empty queue")

    def pop_best(self) -> Optional[Any]:
        while self._max_heap:
            _, _, e = heapq.heappop(self._max_heap)
            if not e.dropped:
                e.dropped = True
                return e.item
        return None

    def peek_best_utility(self) -> Optional[float]:
        while self._max_heap and self._max_heap[0][2].dropped:
            heapq.heappop(self._max_heap)
        return -self._max_heap[0][0] if self._max_heap else None

    def min_utility(self) -> Optional[float]:
        while self._min and self._min[0].dropped:
            heapq.heappop(self._min)
        return self._min[0].utility if self._min else None


# ---------------------------------------------------------------------------
# Array-backed queue lanes — shared helpers
# ---------------------------------------------------------------------------

def make_lanes(num_cameras: int, capacity: int):
    """Fresh empty (C, K) lanes: (util, seq, next_seq)."""
    return (jnp.full((num_cameras, capacity), -jnp.inf, jnp.float32),
            jnp.full((num_cameras, capacity), -1, jnp.int32),
            jnp.zeros((num_cameras,), jnp.int32))


# ---------------------------------------------------------------------------
# Top-cap selection (the batch push / resize core)
# ---------------------------------------------------------------------------
#
# Sorting candidates ascending by (util, seq) puts empty slots
# ((-inf, -1)) first, then valid entries worst-to-best. With per-row
# counts (n_inval invalid, n_evict to drop), the evicted entries occupy
# sorted positions [n_inval, n_inval + n_evict) and the survivors are
# the final n_keep positions; gathering the last K positions re-packs
# the lanes (sorted ascending, a canonical layout).

def select_dev(util, seq, bidx, keep_cap, K):
    """Device top-cap selection (see module docstring for the contract)."""
    u_s, s_s, b_s = jax.lax.sort((util.astype(jnp.float32),
                                  seq.astype(jnp.int32),
                                  bidx.astype(jnp.int32)),
                                 num_keys=2, dimension=-1)
    total = (seq >= 0).sum(axis=-1).astype(jnp.int32)
    C, M = u_s.shape
    n_keep = jnp.minimum(total, keep_cap)
    n_evict = total - n_keep
    n_inval = M - total
    pos = jnp.arange(M, dtype=jnp.int32)
    evict = ((pos[None, :] >= n_inval[:, None])
             & (pos[None, :] < (n_inval + n_evict)[:, None]))
    evicted_seq = jnp.where(evict, s_s, -1).astype(jnp.int32)
    evicted_bidx = jnp.where(evict, b_s, -1).astype(jnp.int32)
    alive = pos[None, M - K:] >= (M - n_keep)[:, None]
    new_util = jnp.where(alive, u_s[:, M - K:],
                         jnp.float32(-jnp.inf)).astype(jnp.float32)
    new_seq = jnp.where(alive, s_s[:, M - K:], -1).astype(jnp.int32)
    return new_util, new_seq, evicted_seq, evicted_bidx


# ---------------------------------------------------------------------------
# Batch push (vectorized admission)
# ---------------------------------------------------------------------------

def push_batch_dev(util, seq, next_seq, u, admit, cap):
    """Push a (C, T) utility batch (``admit`` masks real pushes) into
    the lanes; equivalent to T sequential heapq pushes per camera.

    Returns (util', seq', next_seq', pushed_seq (C, T),
    evicted_seq (C, K+T), evicted_bidx (C, K+T)): ``pushed_seq`` maps
    batch slots to assigned seqs (-1 not pushed); ``evicted_bidx``
    marks evictions of *this batch's* frames by batch column (-1 for
    evicted pre-batch residents, whose seqs are in ``evicted_seq``).
    """
    C, K = util.shape
    u = flush_subnormal(u, jnp)
    T = u.shape[1]
    npush = jnp.cumsum(admit.astype(jnp.int32), axis=1)
    seq_in = next_seq[:, None] + npush - 1
    cand_u = jnp.concatenate(
        [util, jnp.where(admit, u, jnp.float32(-jnp.inf))], axis=1)
    cand_s = jnp.concatenate([seq, jnp.where(admit, seq_in, -1)],
                             axis=1).astype(jnp.int32)
    tcols = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (C, T))
    cand_b = jnp.concatenate(
        [jnp.full((C, K), -1, jnp.int32), jnp.where(admit, tcols, -1)],
        axis=1)
    cap_eff = jnp.clip(cap, 1, K).astype(jnp.int32)
    pushed_seq = jnp.where(admit, seq_in, -1).astype(jnp.int32)
    new_next = (next_seq + npush[:, -1]).astype(jnp.int32)
    nu, ns, ev_s, ev_b = select_dev(cand_u, cand_s, cand_b, cap_eff, K)
    return nu, ns, new_next, pushed_seq, ev_s, ev_b


# ---------------------------------------------------------------------------
# Single push (the frame-at-a-time offer path)
# ---------------------------------------------------------------------------
#
# No sort: find the first free slot (queue not full) or replace the
# worst entry (full). Replacement keeps the slot layout stable through
# mixed push/pop sequences.

def push_one_dev(util, seq, next_seq, u, do_push, cap):
    """Push u[c] for cameras with do_push[c] (others untouched).

    Returns (util', seq', next_seq', pushed_seq (C,),
    evicted_seq (C,), incoming_evicted (C,) bool): ``evicted_seq`` is
    the evicted entry's seq (== pushed_seq when the incoming frame
    itself lost the comparison; -1 when nothing was evicted).
    """
    C, K = util.shape
    rows = jnp.arange(C)
    u = flush_subnormal(u, jnp)
    valid = seq >= 0
    count = valid.sum(axis=-1)
    cap_eff = jnp.clip(cap, 1, K)
    uv = jnp.where(valid, util, jnp.inf)
    w_util = uv.min(axis=-1)
    w_cand = valid & (uv == w_util[:, None])
    w_slot = jnp.where(w_cand, seq, INT32_MAX).argmin(axis=-1)
    w_seq = seq[rows, w_slot]
    free_slot = jnp.argmax(~valid, axis=-1)
    full = count >= cap_eff
    inc_evicted = do_push & full & (u < w_util)     # tie evicts the resident
    place = do_push & ~inc_evicted
    slot = jnp.where(full, w_slot, free_slot)
    new_util = util.at[rows, slot].set(
        jnp.where(place, u, util[rows, slot]))
    new_seq = seq.at[rows, slot].set(
        jnp.where(place, next_seq, seq[rows, slot]))
    nn = (next_seq + do_push.astype(jnp.int32)).astype(jnp.int32)
    pushed_seq = jnp.where(do_push, next_seq, -1).astype(jnp.int32)
    evicted_seq = jnp.where(
        inc_evicted, next_seq,
        jnp.where(place & full, w_seq, -1)).astype(jnp.int32)
    return new_util, new_seq, nn, pushed_seq, evicted_seq, inc_evicted


# ---------------------------------------------------------------------------
# Resize (Eq. 20 dynamic sizing) and transmission (pop best)
# ---------------------------------------------------------------------------

def resize_dev(util, seq, cap):
    """Shrink each row to ``clip(cap, 1, K)`` entries, evicting lowest
    (util, seq) first. Returns (util', seq', evicted_seq (C, K))."""
    K = util.shape[1]
    cap_eff = jnp.clip(cap, 1, K).astype(jnp.int32)
    nu, ns, ev_s, _ = select_dev(util, seq, jnp.full_like(seq, -1),
                                 cap_eff, K)
    return nu, ns, ev_s


def pop_best_dev(util, seq, cam=None):
    """Pop the best (max utility, oldest seq) entry of camera ``cam``,
    or — cam=None — of the whole array (lowest camera index breaks
    utility ties, matching a sequential strict-``>`` scan).

    Returns (util', seq', cam (int32), popped_seq (int32)); negative
    ``popped_seq`` means every candidate queue was empty.
    """
    valid = seq >= 0
    bu = jnp.where(valid, util, jnp.float32(-jnp.inf)).max(axis=-1)
    has = valid.any(axis=-1)
    slot = jnp.where(valid & (util == bu[:, None]), seq,
                     INT32_MAX).argmin(axis=-1).astype(jnp.int32)
    if cam is None:
        c = jnp.argmax(bu).astype(jnp.int32)
        ok = has.any()
    else:
        c = jnp.asarray(cam, jnp.int32)
        ok = has[c]
    s = slot[c]
    popped_seq = jnp.where(ok, seq[c, s], -1).astype(jnp.int32)
    new_util = util.at[c, s].set(jnp.where(ok, -jnp.inf, util[c, s]))
    new_seq = seq.at[c, s].set(jnp.where(ok, -1, seq[c, s]))
    return new_util, new_seq, jnp.where(ok, c, -1).astype(jnp.int32), popped_seq


# ---------------------------------------------------------------------------
# Batched top-k pop (device-side transmission control)
# ---------------------------------------------------------------------------
#
# k sequential pop_best(cam=None) calls emit entries in the strict
# lexicographic order (utility desc, camera asc, seq asc) — a total
# order, since (cam, seq) is unique among live entries. One sort over
# the flattened (C*K,) lanes therefore reproduces the whole sequence:
# on device a single variadic ``lax.sort`` with keys (-util, cam, seq)
# IS the top-k selection (``lax.top_k`` itself lowers to this sort, and
# with x64 disabled no single 32-bit key can carry the two-level
# tiebreak). Utilities are canonicalized with ``u + 0.0`` (folds -0.0
# into +0.0, exact for every other float) so ±0 ties break by (cam,
# seq) exactly like the scalar pop's ``==`` mask; float negation is
# exact and order-reversing for the remaining values.

def pop_topk_dev(util, seq, k: int, rows=None):
    """Pop the ``min(k, C*K)`` best entries of the (C, K) lanes in ONE
    device dispatch — exactly the sequence ``k`` sequential
    :func:`pop_best_dev` (cam=None) calls would pop.

    rows: optional (C,) bool mask restricting candidate cameras.
    Returns (util', seq', cams, seqs): popped identities padded with -1
    past the number of live entries (found entries form a prefix).
    """
    C, K = util.shape
    kk = min(int(k), C * K)
    valid = seq >= 0
    if rows is not None:
        valid = valid & rows[:, None]
    nu = jnp.where(valid, -(util + jnp.float32(0.0)),
                   jnp.inf).reshape(-1)
    cams = jnp.broadcast_to(
        jnp.arange(C, dtype=jnp.int32)[:, None], (C, K)).reshape(-1)
    seqs = jnp.where(valid, seq, INT32_MAX).reshape(-1)
    slots = jnp.broadcast_to(
        jnp.arange(K, dtype=jnp.int32)[None, :], (C, K)).reshape(-1)
    nu_s, cam_s, seq_s, slot_s = jax.lax.sort(
        (nu, cams, seqs, slots), num_keys=3)
    found = nu_s[:kk] < jnp.inf          # live utilities are finite
    pc = jnp.where(found, cam_s[:kk], -1).astype(jnp.int32)
    ps = jnp.where(found, seq_s[:kk], -1).astype(jnp.int32)
    ic = jnp.where(found, cam_s[:kk], C)           # OOB row -> dropped
    new_util = util.at[ic, slot_s[:kk]].set(-jnp.inf, mode="drop")
    new_seq = seq.at[ic, slot_s[:kk]].set(-1, mode="drop")
    return new_util, new_seq, pc, ps


__all__ = [
    "UtilityQueue", "make_lanes", "flush_subnormal",
    "select_dev", "push_batch_dev", "push_one_dev", "resize_dev",
    "pop_best_dev", "pop_topk_dev",
]
