"""Reference operation `update_stream`: `write_read`'s pair (LDBC SNB
Interactive update IU8, add friendship, then short read IS3, friends of
a person, from the same person) as the specification's update stream
replays it: split into PARTITIONS that run concurrently, each owning its
persons.  A source is written by one session only, so "the last write
per (src, dst, rank 0) wins" is one session's order.

Everything that decides an answer is `write_read`'s (imported, not
copied): the book of acknowledged writes, the 3 new edges to 1
overwrite, the running `w`, the `f` float32 cannot hold.  What this
file adds, all behind ONE lock because eight sessions call in at once:

* ownership held to: a source has ONE write in flight at a time (the
  driver gives each source to one session); a second thread that asks
  for a write from it before the first is acknowledged is an error of
  the harness's, raised;
* draws per SOURCE (a generator seeded by the run's tables and the
  source): what a source writes does not depend on how the sessions
  interleave, so the rows every delta buffer holds after k rounds are
  the seed's;
* the expected answer of a read-back is FIXED when its write is
  acknowledged (`acknowledged` keeps it): later writes from other
  sources cannot touch it, and the check compares the source's last
  reply with exactly that;
* the backlog a deployment that has been up for hours carries
  (`backlog`, `backlog_acknowledged`): acknowledged new edges from
  sources drawn as the mix draws its own (uniformly over the persons
  with an out-edge), noted in the book as any acknowledged write is.

numpy only; imports nothing of the program."""
from __future__ import annotations

import threading

import numpy as np

from benchmarks.lib import loader

_wr = loader.module("reference/ops", "write_read")
_LOCK = threading.RLock()

compare = _wr.compare


def _book(ref=None, t=None):
    """write_read's book of this run, with this file's state on it."""
    book = _wr._BOOK if ref is None else _wr._book(ref, t)
    if not hasattr(book, "writing"):
        book.writing, book.draws, book.expected = {}, {}, {}
        b = book.ref.csr[book.t["over"][0]]
        book.salt = [int(b.nbr[:256].sum()), int(b.nbr.size)]
    return book


def count(ref, t, start):
    with _LOCK:
        _book(ref, t)
        return _wr.count(ref, t, start)


def answer(ref, t, start):
    """The read-back's rows as fixed at the source's last acknowledged
    write (the generator's rows where it has none yet)."""
    with _LOCK:
        kept = _book(ref, t).expected.get(start)
        return kept if kept is not None else _wr.answer(ref, t, start)


def _draws_of(book, src):
    rng = book.draws.get(src)
    if rng is None:
        rng = book.draws[src] = np.random.default_rng(
            book.salt + [int(src) + 1, 0x75737472])
    return rng


def next_write(request):
    """`write_read.next_write`, drawn from the source's own generator."""
    with _LOCK:
        book, v = _book(), request["start"]
        me = threading.get_ident()
        if book.writing.setdefault(v, me) != me:
            raise RuntimeError(f"update_stream: source {v} is written by two sessions at once")
        shared, book.rng = book.rng, _draws_of(book, v)
        try:
            return _wr.next_write(request)
        finally:
            book.rng = shared


def acknowledged(request, write):
    with _LOCK:
        book, v = _book(), request["start"]
        _wr.acknowledged(request, write)
        book.expected[v] = _wr.answer(book.ref, request["template"], v)
        book.writing.pop(v, None)


def rows_now(request):
    with _LOCK:
        return _wr.rows_now(request)


def probe_request():
    with _LOCK:
        _book()
        return _wr.probe_request()


# -- the backlog: what the deployment took in since its last compaction ------


def backlog(n, sources=None):
    """`n` writes of NEW edges, each {"src", "dst", "w", "f"}, from
    sources drawn uniformly over the persons with an out-edge (as
    `lib/requests.py` draws a mix's; over the first `sources` of a
    seeded shuffle of them where a rehearsal asks for fewer), and the
    text of the ONE statement that inserts them.  Nothing is noted until
    `backlog_acknowledged`."""
    with _LOCK:
        book = _book()
        t, ref = book.t, book.ref
        b = ref.csr[t["over"][0]]
        rng = _draws_of(book, -1)
        if not hasattr(book, "pool"):
            book.pool = rng.permutation(np.flatnonzero(np.diff(b.indptr) >= 1))
        pool = book.pool if sources is None else book.pool[:int(sources)]
        out, batch = [], {}
        for _ in range(int(n)):
            v = int(pool[rng.integers(pool.size)])
            known = batch.setdefault(v, set(b.nbr[b.indptr[v]:b.indptr[v + 1]].tolist())
                                     | set(book.acked.get(v, ())) | {v})
            if len(known) >= ref.n:
                continue
            while True:
                dst = int(rng.integers(ref.n))
                if dst not in known:
                    break
            known.add(dst)
            while True:
                f = float(rng.random())
                if float(np.float32(f)) != f:
                    break
            out.append({"src": v, "dst": dst, "w": book.next_w + book.sent, "f": f})
            book.sent += 1
        head, row = t["write"].split(" VALUES ")
        text = head + " VALUES " + ", ".join(
            row.replace("$v", str(w["src"])).replace("$u", str(w["dst"]))
            .replace("$w", str(w["w"])).replace("$f", repr(w["f"])) for w in out)
        return out, text


def backlog_acknowledged(writes):
    with _LOCK:
        book = _book()
        for w in writes:
            req = {"start": w["src"], "template": book.t}
            _wr.acknowledged(req, w)
            book.expected[w["src"]] = _wr.answer(book.ref, book.t, w["src"])


def read_of(src):
    """The IS3 read-back from `src` as a request of this run's template."""
    with _LOCK:
        book = _book()
        return {"template": book.t, "start": src, "idx": 0,
                "text": book.t["text"].replace("$v", str(src)),
                "rows": _wr.count(book.ref, book.t, src)}
