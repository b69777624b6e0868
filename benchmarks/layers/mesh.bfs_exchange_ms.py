"""`mesh.bfs_exchange_ms` — mesh exchange (tpu/hop.py `_exchange_marks`
after EVERY level of the sharded BFS program, and whatever else the
compiler makes a collective of): seconds of the collective operations on
each chip's `XLA Ops` line inside the traced slice, mean over the chips'
planes, per traced statement.  A collective's seconds on one chip are its
transfer and its wait for the slowest chip to arrive: a level's exchange
is well under a megabyte a chip, so what is read here is mostly how long
the other chips wait for the fullest part's trips.

Matched on the OPCODE of the instruction's text (`all-to-all`,
`all-gather`, `all-reduce`, each also as `-start` / `-done`), as
`mesh.exchange_ms` matches, because a fusion that merely consumes the
result carries the instruction's name among its operands."""
import re

from benchmarks.lib import trace as T

_OPCODE = re.compile(r"\s(all-to-all|all-gather|all-reduce)(-start|-done)?\(")
_BARE = re.compile(r"%?(all-to-all|all-gather|all-reduce)")


def is_collective(name: str) -> bool:
    return bool(_OPCODE.search(name) if " = " in name else _BARE.match(name))


def read(ctx):
    events, traced = ctx["events"], ctx["traced"]
    bounds = T.window(events) if events else None
    if bounds is None or not traced:
        return None
    t0, t1 = bounds
    per_chip = [sum(min(e, t1) - max(s, t0) for name, s, e in ops
                    if e > t0 and s < t1 and is_collective(name))
                for ops in events["devices"].values()]
    if not any(per_chip):
        return None         # a program with no collective: nothing to read
    return sum(per_chip) / len(per_chip) / 1e6 / len(traced)
