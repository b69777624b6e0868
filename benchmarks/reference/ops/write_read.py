"""Reference operation `write_read`: one request is a PAIR — LDBC SNB
Interactive update IU8 (add friendship: `INSERT EDGE KNOWS(w, f) VALUES
v->u:(w, f)`) and then short read IS3 (friends of a person: `GO 1 STEPS
FROM v OVER KNOWS YIELD dst, w, f`) from the same person.  The answer of
the read grows with the writes the system has ACKNOWLEDGED, so this
operation keeps the harness's own list of them: the builder's session asks
`next_write` what to send, and calls `acknowledged` once the system has
acknowledged it — nothing here is ever read back from the program.

numpy over the generator's tables plus that list; the last write per
(src, dst, rank 0) wins.  Imports nothing of the program.

Of every `new_of` requests of the list (by its index) all but the last
insert a NEW edge: `u` is drawn among the persons that are not yet a
neighbour of `v`, a fresh one at every replay; the last OVERWRITES an
edge the generator made (same (src, dst, rank 0): a tombstone and a new
row to a store that keeps its base immutable).  `w` is a running number
above every generated `w`, so the row of the largest `w` in an answer is
the source's newest acknowledged write (controls/stale_read.py drops it);
`f` is a double that float32 cannot hold (controls/f32.py breaks it).
The draws come from a generator seeded by the seed's own tables."""
from __future__ import annotations

import numpy as np

from benchmarks.reference.graph import same_rows

COLS = ("d", "w", "f")


class _Book:
    """What the harness knows of one run: the reference graph it was
    made for, the template, the writes acknowledged so far per source
    (dst -> (w, f), in order) and which of their dsts are new edges."""

    def __init__(self, ref, t):
        self.ref, self.t = ref, t
        b = ref.csr[t["over"][0]]
        self.rng = np.random.default_rng(
            [int(b.nbr[:256].sum()), int(b.nbr.size), 0x69753869])
        self.next_w = int(b.w.max(initial=0)) + 1000
        self.acked = {}             # src -> {dst: (w, f)}, insertion order
        self.new = {}               # src -> set of dsts the generator lacks
        self.sent = 0


_BOOK = None


def _book(ref, t):
    """The book of this run: a new reference graph is a new run."""
    global _BOOK
    if _BOOK is None or _BOOK.ref is not ref:
        _BOOK = _Book(ref, t)
    return _BOOK


def _base(ref, t, start):
    return ref.go([start], 1, t["over"], None, COLS)[0]


def count(ref, t, start):
    """Rows of the read-back now: the generator's rows of the source
    plus the new edges acknowledged from it so far."""
    book = _book(ref, t)
    return ref.go([start], 1, t["over"], None, COLS, count_only=True)[1] \
        + len(book.new.get(start, ()))


def answer(ref, t, start):
    """The read-back's rows now: every edge of the source, each with its
    newest acknowledged values."""
    book = _book(ref, t)
    cols = _base(ref, t, start)
    d, w, f = (cols[c].copy() for c in COLS)
    at = {int(v): i for i, v in enumerate(d.tolist())}
    more = []
    for dst, (nw, nf) in book.acked.get(start, {}).items():
        if dst in at:
            w[at[dst]], f[at[dst]] = nw, nf
        else:
            more.append((dst, nw, nf))
    if more:
        d = np.concatenate([d, np.asarray([m[0] for m in more], d.dtype)])
        w = np.concatenate([w, np.asarray([m[1] for m in more], w.dtype)])
        f = np.concatenate([f, np.asarray([m[2] for m in more], f.dtype)])
    return {"d": d, "w": w, "f": f}


def compare(reply, want):
    """-> (rows that differ, widest float gap or None, detail)"""
    return same_rows({c: reply.column(c) for c in want}, want)


# -- the harness's side of the write ---------------------------------------


def next_write(request):
    """The write of this request, to be sent before its read-back:
    {"dst", "w", "f", "text"}.  Nothing is noted until `acknowledged`."""
    book, t, v = _BOOK, request["template"], request["start"]
    ref = book.ref
    b = ref.csr[t["over"][0]]
    base = b.nbr[b.indptr[v]:b.indptr[v + 1]]
    if request["idx"] % int(t["new_of"]) == int(t["new_of"]) - 1:
        dst = int(base[book.rng.integers(base.size)])      # overwrite
    else:
        known = set(base.tolist()) | set(book.acked.get(v, ())) | {v}
        while True:
            dst = int(book.rng.integers(ref.n))
            if dst not in known:
                break
    while True:
        f = float(book.rng.random())
        if float(np.float32(f)) != f:
            break
    w = book.next_w + book.sent
    book.sent += 1
    text = t["write"].replace("$v", str(v)).replace("$u", str(dst)) \
        .replace("$w", str(w)).replace("$f", repr(f))
    return {"dst": dst, "w": w, "f": f, "text": text}


def acknowledged(request, write):
    """The system acknowledged `write` (of `next_write`): from now on it
    belongs to every answer from its source."""
    book, v = _BOOK, request["start"]
    b = book.ref.csr[request["template"]["over"][0]]
    per = book.acked.setdefault(v, {})
    per.pop(write["dst"], None)                 # the newest goes last
    per[write["dst"]] = (write["w"], write["f"])
    if write["dst"] not in b.nbr[b.indptr[v]:b.indptr[v + 1]]:
        book.new.setdefault(v, set()).add(write["dst"])


def rows_now(request):
    """What `count` gives now, for the request's source."""
    return count(_BOOK.ref, request["template"], request["start"])


def probe_request():
    """A request of this run's template from its first eligible source,
    for a builder that tries one pair before the warm-up."""
    book = _BOOK
    t = book.t
    b = book.ref.csr[t["over"][0]]
    v = int(np.flatnonzero(np.diff(b.indptr) >= 1)[0])
    return {"template": t, "start": v, "idx": 0,
            "text": t["text"].replace("$v", str(v)),
            "rows": count(book.ref, t, v)}
