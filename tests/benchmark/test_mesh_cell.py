"""Tier-1 tests of what `snb-sf300-proxy.go3-4chip` brings to the
benchmark (PR 28): the `knows_symmetric` generator, the `prebuilt_mesh`
builder against `prebuilt_snapshot` and against the HBM budget (by shape
arithmetic, no allocation), the sharded runtime against the local one and
the plain reference on the rehearsal graph, and each new per-layer reader
on a hand-built `ctx`.  The cell's own rehearsals, control look-ups and
pieces test are test_benchmark.py's parametrised cases."""
from __future__ import annotations

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import arith, loader, trace as T  # noqa: E402
from benchmarks.reference.graph import RefGraph  # noqa: E402

CONFIG, MIX = "snb-sf300-proxy", "go3-mesh-2s"
BIG_SEED = 2 ** 31 + 2828
CFG = loader.data("configs", CONFIG)
SCHEMA = CFG["fixes"]["schema"]["edges"]
GEN = loader.module("reference/generators", "knows_symmetric")
MESH = loader.module("builders", "prebuilt_mesh")
TEMPLATE = loader.data("traffic", MIX)["templates"][0]


def knows(sizes, seed=BIG_SEED):
    return GEN.generate(sizes, seed)["edges"]["KNOWS"]


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


def test_generator_repeats_per_seed_and_takes_a_seed_over_2_to_the_31():
    a, b, c = knows(CFG["rehearse"]), knows(CFG["rehearse"]), knows(CFG["rehearse"], BIG_SEED + 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert a["src"].size != c["src"].size or not np.array_equal(a["src"], c["src"])
    assert set(a) == {"src", "dst", "w", "f", "city"} and a["f"].dtype == np.float64
    assert a["city"].max() < len(GEN.NAMES) and 0 <= a["w"].min() and a["w"].max() < 100


def test_generator_emits_every_friendship_in_both_directions():
    e = knows(CFG["rehearse"])
    half = e["src"].size // 2
    assert e["src"].size == 2 * half
    assert np.array_equal(e["src"][:half], e["dst"][half:])       # row i + rows/2 mirrors row i
    assert np.array_equal(e["dst"][:half], e["src"][half:])
    assert not (e["src"] == e["dst"]).any()                       # no self-pair
    # the two directions draw their own properties
    assert not np.array_equal(e["f"][:half], e["f"][half:])


@pytest.mark.parametrize("degree,max_degree,persons", [(8, 64, 20_000), (30, 1000, 12_000)])
def test_largest_degree_is_the_cap_whatever_the_size_and_the_mean_is_the_configurations(
        degree, max_degree, persons):
    sizes = {"persons": persons, "degree": degree, "max_degree": max_degree}
    small = np.bincount(knows(sizes)["src"], minlength=persons)
    large = np.bincount(knows(dict(sizes, persons=10 * persons))["src"], minlength=10 * persons)
    # social_arrays' hub grows with the edge count (PERF.md); this one's does not
    assert small.max() <= max_degree and large.max() <= max_degree
    if degree == 8:       # 0.5% of the persons draw over this cap: it is reached at either size
        assert small.max() == large.max() == max_degree
    assert abs(large.mean() / degree - 1) < 0.02, large.mean()
    # a neighbour is met in proportion to its degree: the far end's mean degree is about e x
    far = large[knows(dict(sizes, persons=10 * persons))["dst"]].mean()
    assert 2.0 < far / degree < 3.2, far


def test_mu_solves_the_clipped_mean():
    for degree, cap in ((8, 64), (30, 1000)):
        assert GEN.clipped_mean(GEN.mu_for(degree, cap), 1.0, cap) == pytest.approx(degree, rel=1e-9)
    with pytest.raises(ValueError):
        GEN.generate({"persons": 50, "degree": 8, "max_degree": 64}, 1)


# ---------------------------------------------------------------------------
# the builder's snapshot
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tables():
    return GEN.generate(CFG["rehearse"], BIG_SEED)


@pytest.fixture(scope="module")
def snap(tables):
    return MESH.snapshot_from_pairs(tables, SCHEMA, int(CFG["rehearse"]["parts"]), MESH.SPACE)


def _records(block, P):
    """Every real slot of a block as (part, local vertex, neighbour, w, f,
    city), sorted: what a layout holds, whatever the order of ties."""
    out = []
    for p in range(P):
        k = int(block.indptr[p, -1])
        local = np.repeat(np.arange(block.indptr.shape[1] - 1), np.diff(block.indptr[p]))
        out.append(np.stack([np.full(k, p), local, block.nbr[p, :k], block.props["w"][p, :k],
                             block.props["f"][p, :k].view(np.int64),
                             block.props["city"][p, :k]], axis=1))
    rec = np.concatenate(out)
    return rec[np.lexsort(rec.T[::-1])]


@pytest.mark.parametrize("direction", ["out", "in"])
def test_the_snapshot_is_what_prebuilt_snapshot_lays_out(tables, snap, direction):
    """One sort per part and the in-block read off the out-block's order
    give what two lexsorts over every row give."""
    plain = loader.module("builders", "prebuilt_snapshot")
    P = int(CFG["rehearse"]["parts"])
    want = plain.snapshot_from_arrays(tables, SCHEMA, P, MESH.SPACE)[0]
    a, b = snap.blocks[("KNOWS", direction)], want.blocks[("KNOWS", direction)]
    assert np.array_equal(a.indptr, b.indptr) and np.array_equal(a.nbr, b.nbr)
    assert np.array_equal(_records(a, P), _records(b, P))
    assert a.nbr.shape == b.nbr.shape and (a.nbr[:, -1] == -1).all()      # padded alike
    assert np.isnan(a.props["f"][0, -1]) and a.props["w"][0, -1] == -2
    assert snap.hbm_bytes() == want.hbm_bytes() == MESH.snapshot_bytes(
        tables["n"], P, a.nbr.shape[1], SCHEMA["KNOWS"])
    assert (snap.vmax, snap.num_parts) == (want.vmax, want.num_parts)


def test_a_table_that_is_not_symmetric_is_refused():
    t = loader.module("reference/generators", "social_arrays").generate(
        {"persons": 500, "degree": 6}, 3)
    with pytest.raises(ValueError):
        MESH.snapshot_from_pairs(t, SCHEMA, 4, MESH.SPACE)


def test_full_size_is_over_one_chips_budget_and_under_it_on_four_by_shapes_alone():
    """No array of the full size is made: `snap.hbm_bytes()` from the
    shapes, held against the program's own check."""
    from nebula_tpu.tpu.runtime import TpuRuntime, TpuUnavailable
    from nebula_tpu.utils.memtracker import get_config

    sizes = CFG["sizes"]
    limit = int(get_config().get("tpu_hbm_limit_bytes"))
    rows = sizes["persons"] * sizes["degree"]
    # the mean degree is within 2% of `degree` and the parts are vertex ids modulo 4:
    # every seed's fullest part rounds up to the same width, so every seed compiles alike
    width = MESH.padded_width(rows // sizes["parts"])
    assert width == 50_331_648
    assert {MESH.padded_width(int(f * rows / sizes["parts"])) for f in (0.97, 1.0, 1.03)} == {width}
    need = MESH.snapshot_bytes(sizes["persons"], sizes["parts"], width, SCHEMA["KNOWS"])
    assert need == 12_932_901_936
    assert need > 1.05 * limit and need > 12_000_000_000      # ISSUE 28's rule, both halves
    assert -(-need // sizes["parts"]) < limit / 3             # a quarter a chip, with room
    shaped = types.SimpleNamespace(num_parts=sizes["parts"], hbm_bytes=lambda: need, space="snb")
    with pytest.raises(TpuUnavailable, match="1 shard"):
        TpuRuntime(n_devices=1)._check_hbm_budget(shaped, "snb")
    TpuRuntime(n_devices=sizes["parts"])._check_hbm_budget(shaped, "snb")     # accepted


# ---------------------------------------------------------------------------
# sharded against local against the reference
# ---------------------------------------------------------------------------


def _session(snap, n_devices):
    from nebula_tpu.tpu.runtime import TpuRuntime
    plain = loader.module("builders", "prebuilt_snapshot")
    rt = TpuRuntime(n_devices=n_devices)
    rt.pin_prebuilt(snap)
    return rt, plain.Session(rt, plain.SnapshotStore(snap))


def test_sharded_local_and_reference_return_the_same_rows(tables, snap):
    from nebula_tpu.tpu.hop import a2a_payload_bytes
    ref = RefGraph(tables, CFG["reference"]["dedupe_last"])
    P = snap.num_parts
    go = loader.module("reference/ops", "go")
    deg = ref.out_degree("KNOWS")
    csr = ref.csr["KNOWS"]

    def second_frontier(v):
        first = np.unique(csr.nbr[csr.indptr[v]:csr.indptr[v + 1]])
        return np.unique(np.concatenate([csr.nbr[csr.indptr[u]:csr.indptr[u + 1]] for u in first]))
    spread = next(v for v in np.flatnonzero(deg >= 2).tolist()
                  if set((second_frontier(v) % P).tolist()) == set(range(P)))
    starts = [spread, int(np.argmax(deg)), int(np.flatnonzero(deg == 1)[0])]
    mesh_rt, mesh = _session(snap, P)
    local_rt, local = _session(snap, 1)
    assert not mesh_rt.local_mode and mesh_rt.mesh_size == P and local_rt.local_mode
    try:
        for v in starts:
            req = {"template": TEMPLATE, "start": v}
            want = go.answer(ref, TEMPLATE, v)
            got_mesh, got_local = mesh.execute(req), local.execute(req)
            assert got_mesh.error is None and got_local.error is None
            assert got_mesh.n_rows == got_local.n_rows == want["d"].size > 0
            for got in (got_mesh, got_local):
                assert go.compare(got, want)[:2] == (0, 0.0)
            st = got_mesh.stats
            # a traverse skips the last hop's exchange: two of them in three hops
            assert st.shards == P
            assert st.exchange_bytes == 2 * a2a_payload_bytes(P, snap.vmax) > 0
            assert (got_local.stats.shards, got_local.stats.exchange_bytes) == (1, 0)
            assert st.hop_edges == got_local.stats.hop_edges
    finally:
        mesh_rt.unpin(MESH.SPACE)
        local_rt.unpin(MESH.SPACE)


def test_the_builder_stands_up_a_mesh_of_exactly_its_parts(tables):
    """Tier-1 has eight virtual devices; the cell's runtime takes four."""
    said = []
    dep = MESH.build(CFG, CFG["rehearse"], tables, said.append)
    try:
        assert dep.rt.mesh_size == 4 and not dep.rt.local_mode and not dep.served
        assert set(dep.stages) == {"snapshot_s", "pin_s"}
        assert "per chip" in said[0] and "snap.hbm_bytes()" in said[0]
        per_chip = dep.rt.snapshots[MESH.SPACE].shard_hbm_bytes()
        assert len(per_chip) == 4 and len(set(per_chip.values())) == 1
    finally:
        dep.close()


# ---------------------------------------------------------------------------
# the readers, on a hand-built ctx
# ---------------------------------------------------------------------------


def _ns(ms):
    return int(ms * 1e6)


A2A = ("%all_to_all.17 = u32[4,1,46875]{2,1,0:T(1,128)S(1)} all-to-all(%all_to_all.16), "
       "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
USER = "%fusion.9 = pred[1500000]{0} fusion(%all_to_all.17, %p.1), kind=kLoop, calls=%fc.9"


def _ctx(**over):
    events = {"devices": {
        "/device:TPU:0": [("%fusion.1 = s32[8]{0} fusion(%p.0)", _ns(10), _ns(20)), (A2A, _ns(20), _ns(24)),
                          (USER, _ns(24), _ns(30)), ("all-to-all-done.1", _ns(40), _ns(42))],
        "/device:TPU:1": [("%fusion.1 = s32[8]{0} fusion(%p.0)", _ns(10), _ns(14)), (A2A, _ns(14), _ns(24)),
                          (USER, _ns(24), _ns(30)), ("%ar = f32[] all-reduce-start(%x)", _ns(95), _ns(105))]},
        "spans": [], "marks": {T.SLICE_BEGIN: [(0, 0)], T.SLICE_END: [(_ns(100), _ns(100))],
                               T.STMT: [(_ns(5), _ns(50))]}}
    stats = types.SimpleNamespace(hop_edges=[30, 600, 9000], frontier_sizes=[1, 30, 500],
                                  exchange_bytes=6_000_000)
    rec = types.SimpleNamespace(idx=0, stats=stats)
    ctx = {"events": events, "trace": T.reduce(events, sessions=2), "traced": [rec, rec],
           "records": [rec] * 4, "served": False, "chips": 4, "requests": [{"template": TEMPLATE}],
           "peaks": arith.peaks_for("TPU v5 lite"), "schema": CFG["fixes"]["schema"],
           "counter": lambda name: {"tpu_collective_wait_s.sum": 0.010}.get(name, 0)}
    ctx.update(over)
    return ctx


def test_exchange_reader_sums_the_collectives_of_each_plane_by_opcode():
    mod = loader.module("layers", "mesh.exchange_ms")
    assert mod.is_collective(A2A) and mod.is_collective("all-to-all-done.1")
    assert mod.is_collective("%x = (u32[4]{0}, u32[4]{0}) all-reduce-start(%a, %b), channel_id=2")
    assert not mod.is_collective(USER) and not mod.is_collective("%fusion.1 = s32[8]{0} fusion(%p.0)")
    # chip 0: 4 + 2 ms; chip 1: 10 ms + the 5 ms of its all-reduce that lie inside the slice
    assert mod.read(_ctx()) == pytest.approx((6 + 15) / 2 / 2)
    quiet = _ctx()
    quiet["events"] = dict(quiet["events"], devices={
        "/device:TPU:0": [("%fusion.1 = s32[8]{0} fusion(%p.0)", _ns(10), _ns(20))]})
    assert mod.read(quiet) is None                        # a program with no collective
    assert mod.read(_ctx(events=None)) is None and mod.read(_ctx(traced=[])) is None


def test_skew_reader_is_the_busiest_plane_over_the_mean():
    read = loader.module("layers", "mesh.busy_skew").read
    # chip 0 busy [10,30) + [40,42) = 22 ms; chip 1 [10,30) + [95,100) = 25 ms
    assert read(_ctx()) == pytest.approx(25 / 23.5)
    assert read(_ctx(events=None)) is None
    even = _ctx()
    even["events"] = dict(even["events"], devices={
        p: [("a", _ns(10), _ns(20))] for p in ("/device:TPU:0", "/device:TPU:1")})
    assert read(even) == 1.0


def test_mesh_roofline_reader_adds_the_exchange_and_divides_by_all_the_chips():
    read = loader.module("layers", "kernel.hop_roofline.mesh").read
    ctx = _ctx()
    busy_s = ctx["trace"]["busy_s"]
    assert busy_s == pytest.approx(0.0235)                # the mean over the planes
    assert read(ctx) == pytest.approx(100.0 * 2 * (186768 + 6_000_000) / (busy_s * 4 * 819e9))
    one = loader.module("layers", "kernel.hop_roofline").read(ctx)
    assert read(ctx) > one / 4                            # the one-chip reader's bytes, and more
    assert read(_ctx(trace=None)) is None and read(_ctx(peaks=None)) is None


def test_launch_wait_reader_is_the_series_over_the_statements(monkeypatch):
    mod = loader.module("layers", "mesh.launch_wait_ms")
    phases = sys.modules[mod.series_ms.__module__]
    monkeypatch.setattr(phases, "kept", lambda prefix: prefix == "tpu_collective_wait_s.sum")
    assert mod.read(_ctx()) == pytest.approx(10.0 / 4)    # 10 ms over four statements
    monkeypatch.setattr(phases, "kept", lambda prefix: False)
    assert mod.read(_ctx()) is None                       # a program without the series
    assert mod.NEEDS == ("tpu_collective_wait_s.count",)
