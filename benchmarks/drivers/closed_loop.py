"""Driver `closed_loop`: independent sessions that each wait for a reply,
as nebula-bench's virtual users do.  Sessions take the next index of the
fixed request list from one shared counter.

`rounds=N` replays the list N times (warm-up).  `seconds=S` is the
measured window: it closes at the first completion at or after S,
statements still in flight then finish but are outside the window, and the
rate divides by the seconds that really elapsed.  With `whole_rounds` it
closes at the first such completion that also completes a round of the
list: where a window holds about one round of long requests, a rate or a
median over five of six requests depends on which five, and the order is
the seed's.  A reply with an error or with another row count than the
reference's is a failed operation."""
from __future__ import annotations

import threading
import time


class Record:
    __slots__ = ("idx", "session", "t_send", "t_done", "ok", "n_rows", "error",
                 "stats", "in_window")

    def latency_s(self):
        return self.t_done - self.t_send


def run(sessions, requests, seconds=None, rounds=None, hooks=None, whole_rounds=False):
    """-> (records in completion order, last reply per request index,
    t0, t_close) on the time.perf_counter clock."""
    lock = threading.Lock()
    state = {"next": 0, "closed": False, "t_close": None}
    limit = None if rounds is None else rounds * len(requests)
    records, last = [], {}
    errors = []
    t0 = time.perf_counter()

    def loop(sid, session):
        try:
            while True:
                with lock:
                    if state["closed"] or (limit is not None and state["next"] >= limit):
                        return
                    i = state["next"]
                    state["next"] += 1
                req = requests[i % len(requests)]
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
                    if rec.in_window:
                        last[req["idx"]] = reply
                        if seconds is not None and rec.t_done - t0 >= seconds and \
                                (not whole_rounds or len(records) % len(requests) == 0):
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
