"""`mesh.exchange_ms` — mesh exchange (tpu/hop.py: the bit-packed frontier
`all_to_all` between hops, the hub `psum`): seconds of the collective
operations on each chip's `XLA Ops` line inside the traced slice, mean
over the chips, per traced statement.  A collective's seconds on one chip
are its transfer and its wait for the slowest chip to arrive: the
exchange is a few MB, so what is read here is mostly the second.

An operation's name in the trace is its HLO instruction's text
(`%all_to_all.17 = u32[4,1,46875]{...} all-to-all(%all_to_all.16),
channel_id=1, ...`): the match is on the OPCODE (`all-to-all`,
`all-reduce`, each also as `-start` / `-done`), not on the instruction's
name, which a fusion that merely consumes the result carries among its
operands.  A bare name (`all-to-all-done.1`) is matched from its start."""
import re

from benchmarks.lib import trace as T

_OPCODE = re.compile(r"\s(all-to-all|all-reduce)(-start|-done)?\(")
_BARE = re.compile(r"%?(all-to-all|all-reduce)")


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
