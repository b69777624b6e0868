"""Driver `closed_loop_rw_owned`: `closed_loop`'s window and records
for an update stream replayed in PARTITIONS.  Session `s` of `n` takes
the requests `idx % n == s`, in order and round after round, so a
request's source is written by one session only (LDBC SNB's update
stream is split into partitions that the driver replays concurrently,
each owning its persons).

A reply is `ok` at the row count the reference expects at the moment it
is in (`closed_loop_rw`'s rule, imported).  The pairs still in flight
when the window closes finish outside it: they are not timed and not
counted, but their replies enter the CHECK, because their writes are
acknowledged and in the reference's book, so their read-backs are what
the source's last reply has to be.

`rounds=N` replays every session's share N times (warm-up); `seconds=S`
closes the window at the first completion at or after S."""
from __future__ import annotations

import threading
import time

from benchmarks.drivers.closed_loop import Record
from benchmarks.drivers.closed_loop_rw import _Growing


def run(sessions, requests, seconds=None, rounds=None, hooks=None, whole_rounds=False):
    """-> (records in completion order, last reply per request index,
    t0, t_close) on the time.perf_counter clock."""
    if whole_rounds:
        raise ValueError("closed_loop_rw_owned: sessions run apart, a window has no whole round")
    requests = [_Growing(r) for r in requests]
    lock = threading.Lock()
    state = {"closed": False, "t_close": None}
    records, last, errors = [], {}, []
    t0 = time.perf_counter()

    def loop(sid, session):
        own = [r for r in requests if r["idx"] % len(sessions) == sid]
        limit = None if rounds is None else rounds * len(own)
        try:
            done = 0
            while own and (limit is None or done < limit):
                with lock:
                    if state["closed"]:
                        return
                req = own[done % len(own)]
                done += 1
                if hooks is not None:
                    hooks.before(sid, req)
                rec = Record()
                rec.idx, rec.session = req["idx"], sid
                rec.t_send = time.perf_counter()
                reply = session.execute(req)
                rec.t_done = time.perf_counter()
                rec.error, rec.n_rows, rec.stats = reply.error, reply.n_rows, reply.stats
                rec.ok = reply.error is None and reply.n_rows == req["rows"]
                with lock:
                    rec.in_window = not state["closed"]
                    records.append(rec)
                    # in the window or in flight at its close: the
                    # source's newest read-back either way
                    last[req["idx"]] = reply
                    if rec.in_window and seconds is not None and rec.t_done - t0 >= seconds:
                        state["closed"], state["t_close"] = True, rec.t_done
                if hooks is not None:
                    hooks.after(sid, req, rec)
        except BaseException as ex:  # noqa: BLE001 — reported by the caller
            errors.append(ex)
            with lock:
                state["closed"] = True

    threads = [threading.Thread(target=loop, args=(i, s), name=f"bench-session-{i}")
               for i, s in enumerate(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    t_close = state["t_close"] or max((r.t_done for r in records), default=t0)
    return records, last, t0, t_close
