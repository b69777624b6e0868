"""The phase vocabulary PR 39 extended (utils/trace.py): a phase for each
new span name, each new phase once in `PHASES` where a gap's label wants
it, and `self_times` over the shapes the new spans make: a
`device:materialise` that keeps only what its two children leave, and a
zero-length span with a span below it."""
from __future__ import annotations

import pytest

from nebula_tpu.utils import trace


@pytest.mark.parametrize("name,phase", [
    ("query:tpu.traverse", "other"), ("query:tpu.traverse_hops", "other"),
    ("query:tpu.bfs", "other"),
    ("tpu:prep", "exec"), ("tpu:launch", "exec"), ("tpu:seed_prep", "exec"),
    ("tpu:launch_account", "exec"), ("tpu:fetch_warm", "exec"),
    ("device:fetch.rows", "fetch"), ("device:fetch", "fetch"),
    ("device:release", "release"),
    ("device:materialise.concat", "mat_concat"), ("device:materialise.decode", "mat_decode"),
    ("device:materialise", "materialise"),
    ("graphd:record", "record"),
    # what the new prefixes must not shadow
    ("device:put", "put"), ("device:delta_put", "delta_apply"), ("graphd:encode", "encode"),
    ("tpu:snapshot_check", "snapshot_check"), ("tpu:shard_exchange", "exec")])
def test_phase_of_each_new_span(name, phase):
    assert trace.phase_of(name) == phase and phase in trace.PHASES
    assert trace.phase_of(name) == phase         # and again, through the cache


def test_each_new_phase_is_in_the_vocabulary_once_and_in_a_labels_order():
    P = trace.PHASES
    for ph in ("release", "mat_concat", "mat_decode", "record"):
        assert P.count(ph) == 1
    assert len(set(P)) == len(P) <= 20 and P[-1] == "other"
    assert P.index("fetch") < P.index("release") < P.index("materialise") \
        < P.index("mat_concat") < P.index("mat_decode")
    assert P.index("encode") < P.index("record")
    # every phase some prefix gives is in the vocabulary
    assert {p for _, p in trace._PHASE_BY_PREFIX if p} <= set(P)


def span(sid, psid, name, t0_us, dur_us):
    return {"tid": "t", "sid": sid, "psid": psid, "name": name, "svc": "tpu",
            "t0": (1_700_000_000_000_000 + t0_us) / 1e6, "dur_us": dur_us}


def test_self_times_of_a_materialise_with_two_children():
    spans = [span("r", "", "query:tpu.traverse", 0, 1000),
             span("m", "r", "device:materialise", 100, 800),
             span("c", "m", "device:materialise.concat", 150, 300),
             span("d", "m", "device:materialise.decode", 500, 200)]
    assert trace.self_times(spans) == {"r": 200, "m": 300, "c": 300, "d": 200}
    us, n = trace.fold_phases(spans)
    assert us == {"other": 200, "materialise": 300, "mat_concat": 300, "mat_decode": 200}
    assert n == {"other": 1, "materialise": 1, "mat_concat": 1, "mat_decode": 1}
    assert sum(us.values()) == 1000


def test_a_zero_length_span_with_a_span_below_it_reads_zero_and_folds():
    """An empty `device:put` around its `device:launch_wait`, a
    `tpu:launch_account` around its marker: before PR 39 the span below a
    zero-length one had no self time at all and the fold raised."""
    spans = [span("r", "", "query:tpu.traverse", 0, 50),
             span("p", "r", "device:put", 10, 0),
             span("w", "p", "device:launch_wait", 10, 0),
             span("a", "r", "tpu:launch_account", 20, 10),
             span("x", "a", "tpu:shard_exchange", 25, 0)]
    assert trace.self_times(spans) == {"r": 40, "p": 0, "w": 0, "a": 10, "x": 0}
    us, n = trace.fold_phases(spans)
    assert us == {"other": 40, "put": 0, "queue": 0, "exec": 10}
    assert n == {"other": 1, "put": 1, "queue": 1, "exec": 2}


def test_a_replayed_launch_nests_nothing_and_still_closes():
    """A shared launch's member takes the launch's phases as flat
    records under its `tpu:launch`: `device:fetch.rows` and the transfer
    that ran inside it arrive as overlapping siblings: the group's wall
    time (650 of 750 us of spans) is split in proportion to length, so
    the phases still sum to the root and `fetch` keeps its share."""
    spans = [span("r", "", "query:tpu.traverse", 0, 1000),
             span("l", "r", "tpu:launch", 0, 900),
             span("f", "l", "device:fetch", 100, 200),
             span("g", "l", "device:fetch.rows", 300, 400),
             span("h", "l", "device:fetch", 350, 100),      # inside `g`, as a sibling
             span("z", "l", "device:release", 700, 50)]
    us, n = trace.fold_phases(spans)
    assert sum(us.values()) == 1000
    assert us == {"other": 100, "exec": 250, "fetch": round(700 * 650 / 750),
                  "release": round(50 * 650 / 750)}
    assert n["fetch"] == 3
