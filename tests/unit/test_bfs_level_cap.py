"""A BFS level's budget is bounded by what a level can expand, the
part's padded edge width, not by the ladder's `max_cap` (PR 43): with
`max_cap` set under a level's need, `TpuRuntime.bfs` converges on one
device and on a mesh of four to the levels of a walk over the store,
with no host fallback and no level budget over the part's width, while a
GO whose hop needs more than `max_cap` still ends as it did; and the two
series the accounting of a converged BFS launch gained move by what the
shapes say.
"""
import numpy as np
import pytest

from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import TpuRuntime, make_mesh             # noqa: E402
from nebula_tpu.tpu.bfs import bfs_exchange_bytes            # noqa: E402
from nebula_tpu.tpu.device import TpuUnavailable             # noqa: E402

from test_bfs_by_need import host_levels                     # noqa: E402
from test_sharded import store_p                             # noqa: E402

N, STEPS, SRCS = 400, 6, [1]
LOW_CAP = 128        # under what the fifth level expands in its fullest part
MOVED = ("tpu_bfs_runs", "tpu_bfs_widest_level_slots.sum",
         "tpu_bfs_widest_level_slots.count", "tpu_bfs_exchange_bytes",
         "tpu_all_to_all_bytes", "tpu_kernel_runs", "tpu_escalation_retries")


def _runtime(parts):
    rt = TpuRuntime(make_mesh(parts))
    rt.init_eb, rt.max_cap = 64, LOW_CAP
    return rt


def _moved(c0):
    c1 = stats().snapshot()
    return {k: c1.get(k, 0) - c0.get(k, 0) for k in MOVED}, {
        k: v - c0.get(k, 0) for k, v in c1.items()
        if k.startswith("tpu_host_fallback") and v > c0.get(k, 0)}


@pytest.mark.parametrize("parts", [1, 4], ids=["one-chip", "mesh-of-four"])
def test_a_level_over_max_cap_converges_to_the_parts_width(parts):
    st = store_p(parts, seed=43, n=N, avg_deg=6)
    rt = _runtime(parts)
    c0 = stats().snapshot()
    dist, s = rt.bfs(st, "g", SRCS, ["knows"], "out", STEPS)
    moved, fallback = _moved(c0)
    dev = rt.snapshots["g"]
    assert rt.local_mode == (parts == 1) and rt.mesh_size == parts
    width = max(int(b.nbr.shape[-1]) for b in dev.blocks.values())
    # some level needs more than the cap in its fullest part, and got it:
    # the series holds the most slots one part expanded in one level
    widest = moved["tpu_bfs_widest_level_slots.sum"]
    assert LOW_CAP < widest <= max(s.e_cap) <= width, (widest, s.e_cap, width)
    assert max(s.hop_edges) / parts <= widest <= max(s.hop_edges)
    want = host_levels(st, SRCS, STEPS, lambda props: True)
    got, sd = np.asarray(dist), st.space("g")
    for v in range(N):
        d = sd.dense_id(v)
        assert got[d % parts, d // parts] == want.get(v, -1), v
    assert len(want) > N // 2                 # most of the graph, not a corner of it
    # one converged launch, on the device
    assert not fallback and moved["tpu_kernel_runs"] == moved["tpu_bfs_runs"] == 1
    assert moved["tpu_escalation_retries"] == s.retries > 0
    # the widest level of the fullest part, observed once
    assert moved["tpu_bfs_widest_level_slots.count"] == 1
    # every level's exchange, from the shapes; nothing on one device
    want_bytes = bfs_exchange_bytes(parts, dev.vmax, STEPS) if parts > 1 else 0
    assert want_bytes == (STEPS * parts * parts * -(-dev.vmax // 32) * 4 if parts > 1 else 0)
    assert moved["tpu_bfs_exchange_bytes"] == moved["tpu_all_to_all_bytes"] == \
        s.exchange_bytes == want_bytes
    # the converged ladder is remembered: the next statement climbs nothing
    _, again = rt.bfs(st, "g", SRCS, ["knows"], "out", STEPS)
    assert again.retries == 0 and again.e_cap == s.e_cap


@pytest.mark.parametrize("parts", [1, 4], ids=["one-chip", "mesh-of-four"])
def test_a_traverse_hop_keeps_max_cap(parts):
    """A GO's capture buffers are budget-wide: its ladder stays under
    `max_cap` and a hop that needs more does not converge, as before
    (ROADMAP M6)."""
    st = store_p(parts, seed=43, n=N, avg_deg=6)
    rt = _runtime(parts)
    c0 = stats().snapshot()
    with pytest.raises(TpuUnavailable, match="did not converge"):
        rt.traverse(st, "g", SRCS, ["knows"], "out", STEPS)
    moved, _ = _moved(c0)
    assert moved["tpu_bfs_exchange_bytes"] == moved["tpu_bfs_runs"] == 0
    # a traverse's exchanges are not the BFS programs' share
    rt.max_cap = 1 << 24
    rt.traverse(st, "g", SRCS, ["knows"], "out", STEPS)
    moved, _ = _moved(c0)
    assert moved["tpu_bfs_exchange_bytes"] == 0
    assert (moved["tpu_all_to_all_bytes"] > 0) == (parts > 1)


@pytest.mark.parametrize("parts", [1, 4], ids=["one-chip", "mesh-of-four"])
def test_a_mesh_launch_settles_the_counters_and_the_span_as_a_local_one(
        parts, monkeypatch):
    """The `tpu_bfs_*` counters and the `tpu:launch` attributes of a BFS
    whose levels loop (trips of 64 slots): on a mesh each shard's own
    trips, summed over the parts."""
    from nebula_tpu.utils import trace

    from test_bfs_by_need import TRIP, _trips
    _trips(monkeypatch, TRIP)
    st = store_p(parts, seed=43, n=N, avg_deg=6)
    rt = TpuRuntime(make_mesh(parts))
    rt.init_eb = TRIP
    rt.bfs(st, "g", SRCS, ["knows"], "out", STEPS)          # the ladder
    keys = ("tpu_bfs_runs", "tpu_bfs_levels", "tpu_bfs_levels_bottom_up",
            "tpu_bfs_edges", "tpu_bfs_budget_slots", "tpu_bfs_chunks_run",
            "tpu_bfs_chunks_budget")
    c0 = stats().snapshot()
    _, s = rt.bfs(st, "g", SRCS, ["knows"], "out", STEPS)
    c1 = stats().snapshot()
    moved = {k: c1.get(k, 0) - c0.get(k, 0) for k in keys}
    looped = [e for e in s.e_cap if e > TRIP]
    assert s.retries == 0 and looped and all(e % TRIP == 0 for e in s.e_cap)
    assert moved["tpu_bfs_runs"] == 1 and moved["tpu_bfs_levels"] == STEPS
    assert moved["tpu_bfs_levels_bottom_up"] == sum(s.bottom_up)
    # a dense level goes bottom-up on one chip and, since PR 45, on a mesh
    assert any(s.bottom_up)
    assert moved["tpu_bfs_edges"] == sum(s.hop_edges)
    assert moved["tpu_bfs_chunks_run"] == s.chunks_run > 0
    assert moved["tpu_bfs_chunks_budget"] == s.chunks_budget == \
        parts * sum(e // TRIP for e in looped)
    # slots run: the looped levels' trips, and a one-trip level's whole budget
    assert moved["tpu_bfs_budget_slots"] == s.chunks_run * TRIP + parts * sum(
        e for e in s.e_cap if e <= TRIP)
    assert moved["tpu_bfs_edges"] <= moved["tpu_bfs_budget_slots"]
    # a shard runs its own trips: fewer in all than the fullest part's on every chip
    if parts > 1:
        assert s.chunks_run < s.chunks_budget
    entry = next(trace.trace_store().get(t["tid"])
                 for t in trace.trace_store().list()
                 if t["name"] == "query:tpu.bfs")
    launch = next(x for x in entry["spans"] if x["name"] == "tpu:launch")
    assert launch["attrs"]["kernel"] == "bfs"
    assert {k: launch["attrs"][k] for k in
            ("levels", "bottom_up", "eb", "chunks_run", "chunks_budget")} == {
        "levels": STEPS, "bottom_up": sum(s.bottom_up), "eb": list(s.e_cap),
        "chunks_run": s.chunks_run, "chunks_budget": s.chunks_budget}
