"""Count-window device programs whose order within a key is EVENT TIME
(no operator-layer dependencies).

A count window of ``W`` rows sliding by ``S`` over a key's rows in the
order ``(event time, tie)``: the window that ends at the key's row ``i``
(0-based) folds the rows ``i - W + 1 .. i``.  The pane form
(``ffat_kernels.make_ffat_step``) counts rows as they ARRIVE and keeps
``[K, capacity / gcd(W, S)]`` pane cells a step; this form keeps ROWS: a
key's last ``W - 1`` lifted rows (``hist``, the newest last) and its
row count, dense over ``[0, K)``, and a step costs by the rows it
releases times ``W``, whatever ``K`` is.

One step, per fixed-capacity batch of ``B`` lanes, over ``N = 3 B + B``:

1. ``wf.order``: the rows that WAIT (``pend``: those no watermark has
   passed yet) and the batch's are sorted together by (class, key, event
   time, tie): the rows the watermark has passed (``ts < watermark``:
   nothing older can still come, by the producer's word) in front, key by
   key in time order, then the rows that go on waiting.  A row older than
   a watermark an EARLIER step acted on broke that word: it is counted
   (``n_ooo``) and takes its place among the rows released with it.
2. The released rows are taken a chunk of ``F`` lanes at a time (one
   ``while`` body: a step pays for the rows it releases, not for ``N``).
   ``wf.place``: a chunk's rows are gathered, lifted, ranked within their
   key, and each lane's window laid out as ``W`` columns: from the lanes
   before it where its rank reaches back that far, else from its key's
   ``hist`` row.  ``wf.fire``: the columns are folded (``comb``, oldest
   first) and the rows that end a window fire.  ``wf.ring``: the key's
   newest ``W - 1`` columns go back into ``hist`` and its count grows, at
   the last lane of each key (a key cut by a chunk's end is continued
   from the state the chunk before left).
3. ``wf.ring``: the rows that wait are gathered into ``pend`` (a step in
   which the batch waits whole and nothing else does keeps the batch as
   it is); those beyond its ``3 B`` lanes (:data:`PEND_BATCHES`: a full
   batch whose rows all wait, behind the stragglers of the one before)
   are lost and counted (``n_overflow``: the operator stops the graph).

**Which rows end a window.**  Row ``i`` of a key (its ``i + 1``-th)
ends one where ``(i + 1 - W) % S == 0``: with ``partial`` (leading
partial windows) from the key's first such row on, the window cut at the
key's start; without it only once ``i + 1 >= W``, and the windows left
incomplete when the stream ends are flushed then (:func:`make_count_
ordered_flush`: the upstream rule).  A key's window ids count up from 0.

Every scatter moves 32-bit words into columns of ``hist`` or of the
counts (a 64-bit one costs ten 32-bit ones on a v5e, one by rows five by
columns: ``PERF.md`` section 6, PR 44).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from windflow_tpu.monitoring.recorder import phase
from windflow_tpu.windows.ffat_kernels import (_b, _flag_comb,
                                               _masked_reduce_last,
                                               _shift_leaf)
from windflow_tpu.windows.join_kernels import _time, _words
from windflow_tpu.windows.session_kernels import TS_MIN

#: what a step counts, in the state as int64 scalars
COUNTERS = ("n_ooo", "n_fired", "n_partial", "n_overflow")
#: the released rows are taken so many lanes at a time, where the step's
#: ``N`` lanes divide so; under ``CHUNK_MIN`` lanes they are taken whole
CHUNK_LANES = 16384
CHUNK_MIN = 2048
#: the widest window this form takes (a lane's window is laid out as
#: ``W`` columns; the pane form has no such bound)
MAX_WINDOW = 256
#: lanes of the rows that wait, in input batches
PEND_BATCHES = 3


def chunk_lanes(n: int) -> int:
    """Lanes of one chunk of a step over ``n``: ``n`` itself, halved
    while the half still divides it and reaches :data:`CHUNK_LANES`."""
    f = n
    while f > CHUNK_LANES and f % 2 == 0 and f // 2 >= CHUNK_MIN:
        f //= 2
    return f


def first_fire(W: int, S: int) -> int:
    """Rows a key has seen when its first leading partial window ends:
    the smallest ``c >= 1`` with ``(c - W) % S == 0``."""
    return (W - 1) % S + 1


def make_count_ordered_state(row_spec, agg_spec, K: int, W: int, B: int):
    """Dense per-key rows over ``[0, K)``, the rows that wait
    (``PEND_BATCHES * B`` lanes) and the step's scalars."""
    P = PEND_BATCHES * int(B)
    state = {
        # a key's last W - 1 lifted rows, the newest last; how many
        # stand is min(count, W - 1)
        "hist": jax.tree.map(lambda s: _hist_zeros(s, K, W - 1), agg_spec),
        # rows the key has seen, as two 31-bit words (join_kernels._words)
        "cnt_lo": jnp.zeros((K,), jnp.int32),
        "cnt_hi": jnp.zeros((K,), jnp.int32),
        "pend": {
            "live": jnp.zeros((P,), bool),
            "ts": jnp.zeros((P,), jnp.int64),
            "row": jax.tree.map(
                lambda s: jnp.zeros((P,) + s.shape, s.dtype), row_spec),
        },
        # newest (lateness-adjusted) watermark a step has acted on
        "wm": jnp.full((), TS_MIN, jnp.int64),
    }
    state.update({c: jnp.zeros((), jnp.int64) for c in COUNTERS})
    return state


# ``hist`` holds a leaf as ``[H, K, ...]`` (a key's rows down a column: on
# a v5e a scatter of 16384 columns into ``[9, K]`` int32 takes 0.14 ms, of
# as many rows into ``[K, 9]`` 0.7), and a scalar leaf of 8 bytes as its
# two 32-bit words, ``[2 H, K]`` uint32, the low words first: a 64-bit
# scatter takes 3.5 ms there (PERF.md section 6, PR 44).

def _wordy(leaf, lanes: int = 0) -> bool:
    """``leaf`` (``lanes`` leading axes before one row's shape) is kept
    as words."""
    return jnp.dtype(leaf.dtype).itemsize == 8 and leaf.ndim == lanes


def _hist_zeros(spec, K: int, H: int):
    if _wordy(spec):
        return jnp.zeros((2 * H, K), jnp.uint32)
    return jnp.zeros((H, K) + spec.shape, spec.dtype)


def _to_words(a):
    """``[H, n]`` of an 8-byte dtype -> ``[2 H, n]`` uint32."""
    u = jax.lax.bitcast_convert_type(a, jnp.uint64)
    return jnp.concatenate([(u & 0xFFFFFFFF).astype(jnp.uint32),
                            (u >> 32).astype(jnp.uint32)])


def _from_words(w, dtype):
    h = w.shape[0] // 2
    u = w[:h].astype(jnp.uint64) | (w[h:].astype(jnp.uint64) << 32)
    return jax.lax.bitcast_convert_type(u, dtype)


def _shift_up(a, k: int):
    """Rows ``k`` further down, moved up along axis 0 (what falls off
    the end is never read)."""
    return jnp.concatenate([a[k:], a[:k]]) if k < a.shape[0] else a


def out_capacity(capacity: int) -> int:
    """Lanes of the batch one step hands on: every row it can release
    (the batch's and those that waited) may end a window."""
    return (PEND_BATCHES + 1) * int(capacity)


def make_count_ordered_step(capacity: int, K: int, W: int, S: int,
                            lift: Callable, comb: Callable,
                            key_fn: Optional[Callable],
                            tie_fn: Optional[Callable], partial: bool):
    """Per-batch program: ``step(state, payload, ts, valid, wm_adj) ->
    (state, out, fired, out_ts, held)``.  ``out`` is ``{"key", "wid",
    "value", "last"}`` (``last``: the record that ended the window) over
    :func:`out_capacity` lanes, a row stamped as the record that ended
    it.  ``held`` is int64 ``[3]``: always 0 (every released row leaves
    in its step), the rows LOST for want of room among those that wait,
    and the rows the batch brought.  The end of stream is the same
    program on an empty batch under the watermark ``TS_MAX``."""
    B, K, W, S = int(capacity), int(K), int(W), int(S)
    P = PEND_BATCHES * B
    N = P + B
    F = chunk_lanes(N)
    H = W - 1
    W0 = first_fire(W, S) if partial else W

    def keys_of(rows, n):
        with phase("wf.fn"):
            keys = jax.vmap(key_fn)(rows).astype(jnp.int32) \
                if key_fn is not None else jnp.zeros(n, jnp.int32)
            # an int32 tie is one operand of the sort, an int64 two (a
            # TPU sort compiles ~16 s a 32-bit operand)
            tie = jax.vmap(tie_fn)(rows) if tie_fn is not None \
                else jnp.zeros(n, jnp.int32)
            if tie.dtype != jnp.int32:
                tie = tie.astype(jnp.int64)
        return keys, tie

    def chunk(c, carry, order, skey, rows, tss, n_rel):
        hist, cnt_lo, cnt_hi, out, fired, out_ts, n_fired, n_part = carry
        base = c * F
        with phase("wf.place"):
            idx = jax.lax.dynamic_slice(order, (base,), (F,))
            lane = jnp.arange(F, dtype=jnp.int32)
            live = base + lane < n_rel
            k = jnp.where(live, jax.lax.dynamic_slice(skey, (base,), (F,)),
                          K)
            row = jax.tree.map(lambda a: a[idx], rows)
            t = tss[idx]
        with phase("wf.fn"):
            lifted = jax.vmap(lift)(row)
        with phase("wf.place"):
            kstart = jnp.concatenate(
                [jnp.array([True]), k[1:] != k[:-1]])
            kend = jnp.concatenate([k[1:] != k[:-1], jnp.array([True])])
            rank = lane - jax.lax.cummax(jnp.where(kstart, lane, 0))
            kc = jnp.minimum(k, K - 1)
            cnt0 = _time(cnt_hi[kc], cnt_lo[kc])
            seen = cnt0 + rank                  # rows of its key before it
            # column c of a lane's window: the row `back = W - 1 - c`
            # before it on its key: a lane of the chunk where its rank
            # reaches back that far, else row `c + rank` of its key's hist
            back = jnp.arange(W - 1, -1, -1, dtype=jnp.int32)   # [W]
            in_batch = rank[None, :] >= back[:, None]           # [W, F]
            stands = live[None, :] & (seen[None, :] >= back[:, None])
            reach = jnp.minimum(rank, H)

            def columns(leaf, held):
                near = jnp.stack([_shift_leaf(leaf, int(b), 0)
                                  for b in range(W - 1, -1, -1)])
                if not H:
                    return near
                far = held[:, kc]                               # [H, F, ..]
                if _wordy(leaf, 1):
                    far = _from_words(far, leaf.dtype)
                # each lane's column moved up by its rank, a bit at a time
                for bit in range(int(H).bit_length()):
                    far = jnp.where(_b(((reach >> bit) & 1)[None, :] == 1,
                                       far), _shift_up(far, 1 << bit), far)
                return jnp.concatenate(
                    [jnp.where(_b(in_batch[:H], far), near[:H], far),
                     near[H:]])
            cols = jax.tree.map(columns, lifted, hist)          # [W, F, ..]
        with phase("wf.fire"):
            _, value = _masked_reduce_last(comb, stands, cols, axis=0)
            ends = (seen + 1 - W) % S == 0
            if not partial:
                ends = ends & (seen + 1 >= W)
            fire = live & ends
            part = fire & (seen + 1 < W)
            wid = (seen + 1 - W0) // S
            put = lambda whole, piece: jax.lax.dynamic_update_slice(  # noqa: E731
                whole, piece.astype(whole.dtype),
                (base,) + (0,) * (whole.ndim - 1))
            rec = {"key": kc, "wid": wid, "value": value, "last": row}
            out = jax.tree.map(put, out, rec)
            fired = put(fired, fire)
            out_ts = put(out_ts, jnp.where(fire, t, 0))
            n_fired = n_fired + jnp.sum(fire, dtype=jnp.int64)
            n_part = n_part + jnp.sum(part, dtype=jnp.int64)
        with phase("wf.ring"):
            at = jnp.where(live & kend, k, K)
            if H:
                hist = jax.tree.map(
                    lambda h, col: h.at[:, at].set(
                        _to_words(col[1:]) if _wordy(col, 2) else col[1:],
                        mode="drop"),
                    hist, cols)
            hi, lo = _words(seen + 1)
            cnt_lo = cnt_lo.at[at].set(lo, mode="drop")
            cnt_hi = cnt_hi.at[at].set(hi, mode="drop")
        return (hist, cnt_lo, cnt_hi, out, fired, out_ts, n_fired, n_part)

    def step(state, payload, ts, valid, wm_adj):
        ts = ts.astype(jnp.int64)
        pend = state["pend"]
        cat = lambda a, b: jnp.concatenate([a, b])   # noqa: E731
        rows = jax.tree.map(cat, pend["row"], payload)
        tss = cat(pend["ts"], ts)
        keys, tie = keys_of(rows, N)
        live = cat(pend["live"], valid) & (keys >= 0) & (keys < K)
        wm_now = jnp.maximum(state["wm"], wm_adj)
        with phase("wf.order"):
            fresh = jnp.arange(N) >= P
            ooo = live & fresh & (tss < state["wm"])
            rel = live & (tss < wm_now)
            wait = live & ~rel
            sid = jnp.where(rel, keys, jnp.where(wait, K, K + 1))
            skey, _, _, order = jax.lax.sort(
                (sid, tss, tie, jnp.arange(N, dtype=jnp.int32)), num_keys=3)
            n_rel = jnp.sum(rel, dtype=jnp.int32)
            n_wait = jnp.sum(wait, dtype=jnp.int32)
        with phase("wf.fire"):
            spec = jax.eval_shape(
                lambda p: jax.vmap(lift)(p), payload)
            out = {
                "key": jnp.zeros((N,), jnp.int32),
                "wid": jnp.zeros((N,), jnp.int64),
                "value": jax.tree.map(
                    lambda s: jnp.zeros((N,) + s.shape[1:], s.dtype), spec),
                "last": jax.tree.map(
                    lambda a: jnp.zeros((N,) + a.shape[1:], a.dtype),
                    payload),
            }
            zero = jnp.zeros((), jnp.int64)
            carry = (state["hist"], state["cnt_lo"], state["cnt_hi"], out,
                     jnp.zeros((N,), bool), jnp.zeros((N,), jnp.int64),
                     zero, zero)
        n_chunks = (n_rel + F - 1) // F
        hist, cnt_lo, cnt_hi, out, fired, out_ts, n_fired, n_part = \
            jax.lax.fori_loop(
                0, n_chunks,
                lambda c, carry: chunk(c, carry, order, skey, rows, tss,
                                       n_rel),
                carry)
        with phase("wf.ring"):
            whole = (n_wait == jnp.sum(live & fresh, dtype=jnp.int32)) \
                & ~jnp.any(wait & ~fresh)

            def keep_batch():
                wide = lambda a: jnp.pad(   # noqa: E731
                    a, [(0, P - B)] + [(0, 0)] * (a.ndim - 1))
                return {"live": wide(live[P:]), "ts": wide(ts),
                        "row": jax.tree.map(wide, payload)}

            def gather_waiting():
                at = jax.lax.dynamic_slice(
                    jnp.pad(order, (0, P)), (n_rel,), (P,))
                stays = jnp.arange(P, dtype=jnp.int32) < n_wait
                take = lambda a: jnp.where(   # noqa: E731
                    _b(stays, a[at]), a[at], jnp.zeros_like(a[at]))
                return {"live": stays, "ts": take(tss),
                        "row": jax.tree.map(take, rows)}

            new_pend = jax.lax.cond(whole, keep_batch, gather_waiting)
            lost = jnp.maximum(n_wait - P, 0).astype(jnp.int64)
            counts = {"n_ooo": jnp.sum(ooo, dtype=jnp.int64),
                      "n_fired": n_fired, "n_partial": n_part,
                      "n_overflow": lost}
            new_state = {"hist": hist, "cnt_lo": cnt_lo, "cnt_hi": cnt_hi,
                         "pend": new_pend, "wm": wm_now}
            new_state.update({c: state[c] + counts[c] for c in COUNTERS})
        return new_state, out, fired, out_ts, jnp.stack(
            [jnp.zeros((), jnp.int64), lost,
             jnp.sum(live & fresh, dtype=jnp.int64)])

    return step


def make_count_ordered_flush(K: int, W: int, S: int, comb: Callable,
                             agg_spec, last_spec):
    """End of stream without leading partial windows: the windows a
    key's rows had begun and not filled are fired over the rows they
    have (the upstream rule, ``ffat_kernels.make_ffat_flush``).
    ``flush(state) -> (out, fired, out_ts)`` over ``ceil((W - 1) / S) *
    K`` lanes; such a window was ended by no record, so ``last`` is
    zeros (``last_spec``: one record; ``agg_spec``: one lifted row)."""
    H = W - 1
    M = -(-H // S)
    fc = _flag_comb(lambda newer, older: comb(older, newer))

    @phase("wf.fire")
    def flush(state):
        cnt = _time(state["cnt_hi"], state["cnt_lo"])          # [K]
        hist = jax.tree.map(
            lambda h, s: _from_words(h, s.dtype) if _wordy(s) else h,
            state["hist"], agg_spec)                            # [H, K, ..]
        stand = jnp.arange(H)[:, None] >= H - jnp.minimum(cnt, H)[None, :]
        # suffix folds of the rows that stand: sfx[j] folds hist[j:],
        # the older row first
        flip = lambda a: jnp.flip(a, 0)     # noqa: E731
        _, sfx = jax.lax.associative_scan(
            lambda a, b: fc(*a, *b),
            (flip(stand), jax.tree.map(flip, hist)), axis=0)
        sfx = jax.tree.map(flip, sfx)
        # the first window not yet ended ends at the first count
        # e >= max(cnt + 1, W) with (e - W) % S == 0
        e0 = jnp.maximum(cnt + 1, W)
        e0 = e0 + (-(e0 - W)) % S
        e = e0[None, :] + jnp.arange(M, dtype=jnp.int64)[:, None] * S
        has = cnt[None, :] - (e - W)            # rows the window holds
        fire = has >= 1
        at = jnp.clip(H - has, 0, H - 1).astype(jnp.int32)      # [M, K]

        def pick(a):
            ix = at.reshape((M, K) + (1,) * (a.ndim - 2))
            ix = jnp.broadcast_to(ix, (M, K) + a.shape[2:])
            return jnp.take_along_axis(a, ix, axis=0) \
                .reshape((M * K,) + a.shape[2:])
        out = {
            "key": jnp.broadcast_to(
                jnp.arange(K, dtype=jnp.int32)[None, :], (M, K)).reshape(-1),
            "wid": ((e - W) // S).reshape(-1),
            "value": jax.tree.map(pick, sfx),
            "last": jax.tree.map(
                lambda s: jnp.zeros((M * K,) + s.shape, s.dtype), last_spec),
        }
        return out, fire.reshape(-1), jnp.zeros((M * K,), jnp.int64)

    return flush
