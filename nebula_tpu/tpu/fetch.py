"""The fetch: what crosses back from the device after a launch.

Of a rung's result the host takes the leaves some caller reads
(`_FETCHED`) and, of the capture columns the statement's yields read,
each row's kept prefix (`_Heads`, `_Pieces`); everything else dies on
the device with the rung.  `Fetcher` is what the escalation driver
(tpu/runtime.py `_escalate_locked`) holds of this module: it brings one
rung to the host (`fetch`), compiles a capture's fetch programs before
any timed phase meets one (`warm`), and owns the memory of what each
program last kept, by which the next run's two phases collapse into
one.  The driver knows none of that: it calls, and tells the fetcher
when a space's programs are gone (`forget`).

Nothing here knows the driver, and the arrow never turns round
(`tests/unit/test_tpu_arrows.py`).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graphstore.delta import pow2
from ..utils import trace as _t


# Result keys of a traverse program (hop.py `_traverse`) that say how far
# its by-need loops and member plans engaged, lead + (steps,); summed
# they are the TraverseStats fields of the same names.  A BFS program
# (bfs.py) returns the first two for its level loops.
_ENGAGEMENT = ("chunks_run", "chunks_budget", "plan_run", "plan_budget")

# The result leaves some caller reads, the only ones `Fetcher.fetch` brings to
# the host besides the capture: the ladder's counts and flags, the kept
# counts, the work counters, BFS's depths and the direction each of its
# levels took.  The post-final `frontier` bitmap and its `fcount` stay on
# the device and die with the rung (vmax bools a part: more bytes than a
# mean four-chip statement's rows).
_FETCHED = ("hop_edges", "ovf_expand", "kcount", "frontier_sizes",
            "dist", "bottom_up") + _ENGAGEMENT

# How a capture leaves the device (`Fetcher.fetch`).  A ROW is one index of a
# capture array's lead + (nb,) axes with its own kept count; kept
# entries are a prefix of their row (hop.py `_compact_cap`), so only
# prefixes are shipped, cut by programs that depend on the capture's
# shape and the columns read alone and are compiled when the traverse
# program first runs for those columns (`Fetcher.warm`),
# never when a kept size is first met.
#
# W < 2 * SLICE_MAX, i.e. a hop budget of at most SLICE_MAX (the served
# statements' 8,192 and 65,536 slots; budgets are powers of two, and an
# armed delta plane's tail widens a capture by less than its budget, so
# the plane never moves a capture to the other taker): ONE slice of
# every row, `v[..., :k]`, k a power of two from SLICE_MIN up (the
# whole width last), speculated from the program's last run.
#
# Wider: every row apart, in pieces cut on the device that holds it, so
# the bytes follow each row's own count (not the fullest row's, rounded
# up, for all).  What the chips charged when the constants below were
# settled (PERF.md section 6, PR 31, has the tables): a piece 0.6 ms on
# the four-chip host (its launch and a transfer a column) to 3 ms on
# one chip however small it is (there most of it the split of a whole
# 64-bit operand before the slice, which no capture holds since PR 35:
# a property column is its 32-bit halves, `(2, size)` a piece), a
# byte 0.3 to 0.5 ns, and a second round trip waits behind whatever
# another session has on the chips.  Hence: a row comes in ONE piece of
# the smallest of PIECES that holds it unless a second piece saves
# PIECE_WORTH slots; and a row that last kept at most SPEC_ROWS comes
# speculatively, one piece of at most SPEC_SLOTS, with the meta (the
# median statement of the four-chip cell then makes one round trip),
# while a longer row is not guessed at (a wrong guess would cost more
# than the round trip, a hundredth of its transfer).
SLICE_MIN, SLICE_MAX = 1 << 7, 1 << 16
PIECES = tuple(1 << i for i in range(11, 22))
PIECE_WORTH = 1 << 16
SPEC_SLOTS, SPEC_ROWS = 1 << 15, 1 << 17


@functools.partial(jax.jit, static_argnames="k")
def _head(cap, k: int):
    return {n: v[..., :k] for n, v in cap.items()}


@functools.partial(jax.jit, static_argnames="size")
def _piece(cap, at, size: int):
    """`size` slots of one row of each of these capture columns, from
    `at` = the row's index and the first slot, both traced: one
    executable a capture shape, column set and size.  A start past
    W - size is clamped to it (`lax.dynamic_slice`).  A column comes
    flat, `(size,)`; a property column (one axis more, its halves,
    before the slots) as `(2, size)`: ONE array and one transfer a
    column either way."""
    row = [at[i] for i in range(at.shape[0] - 1)]

    def cut(v):
        halves = (2,) * (v.ndim - at.shape[0])
        return jax.lax.dynamic_slice(
            v, row + [jnp.int32(0)] * len(halves) + [at[-1]],
            (1,) * len(row) + halves + (size,)).reshape(halves + (size,))
    return {n: cut(v) for n, v in cap.items()}


def _nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _taker(cap_dev, want=None):
    """The way this capture's rows leave the device, by its width; of
    its columns the host takes those in `want` (all of them if None)."""
    wide = next(iter(cap_dev.values())).shape[-1] >= 2 * SLICE_MAX
    return (_Pieces if wide else _Heads)(cap_dev, want)


class _Heads:
    """The kept prefixes of a capture whose hop budget is at most
    SLICE_MAX slots (narrower than twice that with a delta plane's
    tail), as one slice of every row.  `speculate(counts)` and `ask(counts)`
    return the device arrays that cover rows of these kept counts (the
    last run's, this run's), or None where there is nothing to ask for
    beyond what was asked before; `got` takes them once on the host;
    `rows` is the fetched capture: per column an object array over the
    rows, of each row's pieces in slot order, trimmed to its kept
    count (a property column's pieces are its halves, `(2, n)`, which
    `_join_halves` joins).  The programs run over the wanted columns
    together (one launch, not one a column), so they are compiled for a
    program's key AND the columns its statement reads
    (`Fetcher.warm`)."""

    def __init__(self, cap_dev, want=None):
        self.dev = cap_dev
        self.want = [n for n in cap_dev if want is None or n in want]
        self.W = next(iter(cap_dev.values())).shape[-1]
        # the axes that index a row, lead + (nb,): all but the slots,
        # and but a property column's halves
        self.nrow = min(v.ndim for v in cap_dev.values()) - 1
        self.k = 0
        self.host: Dict[str, np.ndarray] = {}
        self.nbytes = 0

    def item_bytes(self) -> int:
        """Bytes of one kept entry over the wanted columns (a property
        column's two halves: 8)."""
        return sum(self.dev[n].dtype.itemsize * (self.dev[n].ndim - self.nrow)
                   for n in self.want)

    def _k(self, n: int) -> int:
        return min(self.W, max(SLICE_MIN, pow2(n)))

    def warm(self):
        cols = {n: self.dev[n] for n in self.want}
        for k in sorted({self._k(1 << i)
                         for i in range(self.W.bit_length() + 1)}):
            _head(cols, k)

    def ask(self, counts):
        k = self._k(int(np.max(counts, initial=0)))
        if k <= self.k:
            return None
        self.k = k
        return _head({n: self.dev[n] for n in self.want}, k)

    speculate = ask

    def got(self, host):
        self.host = host
        self.nbytes += _nbytes(host)

    def rows(self, kc):
        cap = {}
        for n, a in self.host.items():
            col = cap[n] = np.empty(kc.shape, object)
            for idx in np.ndindex(kc.shape):
                col[idx] = [a[idx][..., :kc[idx]]]
        return cap


class _Pieces(_Heads):
    """The same of a wider capture, every row in pieces cut on the
    device that holds it: a sharded column is read shard by shard
    (`addressable_shards`), so no slice crosses chips and `device_get`
    assembles nothing.  `ask` cuts only what lies past the pieces
    already asked for: an undershot speculation fetches a tail, never
    the prefix again."""

    def __init__(self, cap_dev, want=None):
        super().__init__(cap_dev, want)
        # where its rows lie in the whole -> a shard's wanted columns
        self.shards: Dict[Tuple, Dict[str, Any]] = {}
        for n in self.want:
            for s in cap_dev[n].addressable_shards:
                if s.replica_id == 0:
                    base = tuple(sl.start or 0
                                 for sl in s.index[:self.nrow])
                    self.shards.setdefault(base, {})[n] = s.data
        self.sizes = [c for c in PIECES if c <= self.W]
        self.have: Dict[Tuple, int] = {}    # row -> slots asked for
        # of each piece asked for: its row, and that it holds the row's
        # slots [slot, slot + c) from its own `skip` on
        self.asked: List[Tuple] = []
        self.host: List[Dict[str, np.ndarray]] = []

    def _size(self, n: int) -> int:
        """The smallest piece that holds n slots (the largest if none)."""
        return next((c for c in self.sizes if c >= n), self.sizes[-1])

    def _cut(self, out, cols, idx, row, slot, c):
        start = min(slot, self.W - c)
        out.append(_piece(cols, np.asarray(idx + (start,), np.int32), c))
        self.asked.append((row, slot, slot - start, c))
        self.have[row] = slot + c

    def _rows(self):
        for base, cols in self.shards.items():
            lead = next(iter(cols.values())).shape[:self.nrow]
            for idx in np.ndindex(lead):
                yield cols, idx, tuple(b + i for b, i in zip(base, idx))

    def warm(self):
        at = np.zeros(self.nrow + 1, np.int32)
        for cols in self.shards.values():
            for c in self.sizes:
                _piece(cols, at, c)

    def speculate(self, counts):
        counts = np.broadcast_to(counts, next(
            iter(self.dev.values())).shape[:self.nrow])
        out = []
        for cols, idx, row in self._rows():
            if 0 < counts[row] <= SPEC_ROWS:
                self._cut(out, cols, idx, row, 0,
                          self._size(min(int(counts[row]), SPEC_SLOTS)))
        return out or None

    def ask(self, counts):
        out = []
        for cols, idx, row in self._rows():
            slot, kept = self.have.get(row, 0), int(counts[row])
            while slot < kept:
                c = self._size(kept - slot)
                half = c // 2
                if half in self.sizes and kept - slot > half and \
                        half - self._size(kept - slot - half) >= PIECE_WORTH:
                    c = half
                self._cut(out, cols, idx, row, slot, c)
                slot += c
        return out or None

    def got(self, host):
        self.host.extend(host)
        self.nbytes += _nbytes(host)

    def rows(self, kc):
        cap = {n: np.empty(kc.shape, object) for n in self.want}
        for col in cap.values():
            for row in np.ndindex(kc.shape):
                col[row] = []
        for (row, slot, skip, c), piece in zip(self.asked, self.host):
            end = skip + min(c, int(kc[row]) - slot)
            if end > skip:
                for n, col in cap.items():
                    col[row].append(piece[n][..., skip:end])
        return cap


class Fetcher:
    """One runtime's fetches, and the speculation memory between them:
    per program key, the kept counts of its last run (`kmax`: they arm
    the single-phase fetch, one device round trip instead of two for a
    repeated query shape; in memory only), and the (program key, columns
    fetched) pairs whose fetch programs are compiled (`warmed`)."""

    def __init__(self):
        self.kmax: Dict[Tuple, Any] = {}
        self.warmed: set = set()

    def forget(self, space: Optional[str] = None) -> None:
        """Drop what is remembered of `space`'s programs (an unpin), or
        of every program (a new mesh: the captures are laid out anew)."""
        if space is None:
            self.kmax.clear()
            self.warmed.clear()
        else:
            self.kmax = {k: v for k, v in self.kmax.items()
                         if k[0] != space}
            self.warmed = {w for w in self.warmed if w[0][0] != space}

    def warm(self, cap_dev, key, fetch_keys: Optional[set],
             phases: list):
        """Compile the fetch programs of this capture (every slice or
        piece size its width admits, on each device that holds a shard)
        when its program first runs for these columns, outside every
        timed phase: no statement meets one for the first time through
        the size of what it kept.  The one statement that does the
        compiling carries it as `tpu:fetch_warm`."""
        wk = (key, None if fetch_keys is None else frozenset(fetch_keys))
        if wk not in self.warmed:
            with _t.phase(phases, "tpu:fetch_warm"):
                _taker(cap_dev, fetch_keys).warm()
            if len(self.warmed) > 4096:
                self.warmed.clear()
            self.warmed.add(wk)

    def fetch(self, res, key, fetch_keys: Optional[set], info):
        """Bring one rung's result to the host: -> (the host result,
        what this frame still held of the device's); the launch's `info`
        takes its phases, undershoots, seconds and bytes.  It times
        itself, as its last statement, and hands the device references it
        took (the leaves, the slices cut of the capture) back to the
        caller, who holds the device result too: releasing device buffers
        waits its turn (tens of ms under eight sessions), is no part of
        the fetch and is timed by the caller as `device:release`.  The
        spans of phase `fetch` cover the clock from end to end:
        `device:fetch` the two transfers (the first with the taker's
        set-up, the second nested), `device:fetch.rows` the host's side
        of a kept capture (the pieces asked for by its kept counts, cut
        on the device and assembled into rows).

        What comes: the leaves a caller reads (`_FETCHED`) and, of the
        capture columns the yields read, each row's kept prefix
        (`_Heads`, `_Pieces`): the transfer follows the rows kept, not
        the edge budget nor the fullest row.  Two-phase on a program's
        first run, and on every run of a wide capture: the small meta
        first, then the prefixes its kept counts name.  SPECULATIVE
        single-phase for the slices after it: what the last run of this
        program (`key`) kept bounds the slice, and both phases collapse
        into ONE device_get.  An undershoot (kept grew past the
        speculation) falls back to the exact refetch and is the one
        refetch counted; an overshoot ships at most what the last run
        needed.  An overflowed rung returns meta alone, a speculative
        slice dropped."""
        t0 = time.perf_counter()
        phases = info["phases"]
        take = first = more = None
        with _t.phase(phases, "device:fetch"):
            meta = {k: res[k] for k in _FETCHED if k in res}
            if "cap" in res:
                take = _taker(res["cap"], fetch_keys)
                spec = self.kmax.get(key)
                first = None if spec is None else take.speculate(spec)
            host, got = jax.device_get((meta, first))
            if first is not None:
                take.got(got)
        info["fetch_bytes"] += _nbytes(host)
        info["refetches"] = 0
        if take is not None and not host["ovf_expand"].any():
            with _t.phase(phases, "device:fetch.rows"):
                kc = host["kcount"]
                more = take.ask(kc)
                if more is not None:
                    # the capture's own fetch: the second phase where
                    # nothing was speculated, else a refetch
                    info["refetches"] = int(first is not None)
                    with _t.phase(phases, "device:fetch",
                                  refetch=first is not None):
                        take.got(jax.device_get(more))
                host["cap"] = take.rows(kc)
                host["cap"]["kcount"] = kc
                info["fetch_bytes_kept"] += int(kc.sum()) * take.item_bytes()
                self.kmax[key] = kc
                while len(self.kmax) > 512:
                    self.kmax.pop(next(iter(self.kmax)))
        if take is not None:
            info["fetch_bytes"] += take.nbytes
        info["fetch_s"] = time.perf_counter() - t0
        return host, (meta, take, first, more)
