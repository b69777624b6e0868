"""The fetch ships what the statement reads (fetch.py `Fetcher.fetch`, PR 31):
of a launch's result the leaves a caller reads (`_FETCHED`), and of each
capture row its kept prefix — one slice of every row where rows are at
most `SLICE_MAX` slots wide (`_Heads`), flat pieces by need cut on the
shard that holds the row where they are wider (`_Pieces`: one small piece
a short row speculatively, then only what lies past it) — through
programs compiled when the traverse program first runs for the columns
read.

The piece path runs here with every capture counted as wide and pieces of
4 to 64 slots (the module's are 2^11 to 2^21), and has to return what the
single-slice path and the host engine return.
"""
import jax
import numpy as np
import pytest

from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.graphstore.delta import pow2                 # noqa: E402
from nebula_tpu.tpu import (TpuRuntime, assemble, fetch,     # noqa: E402
                            make_mesh)
from nebula_tpu.tpu.fetch import Fetcher                     # noqa: E402
from nebula_tpu.tpu.device import split_halves               # noqa: E402

from test_batch import (GO_TMPL, _concurrent, _run_stmt,     # noqa: E402,F401
                        clean, company, device_engine)
from test_delta import store_p                               # noqa: E402
from test_hop_by_need import GO_Q, _rows                     # noqa: E402
from test_tpu import _hubby_store                            # noqa: E402

SIZES = (4, 8, 16, 32, 64)


@pytest.fixture()
def pieces(monkeypatch):
    """Every capture takes the piece path, in pieces small enough that
    these graphs' rows hold several.  Yields the pieces cut, as (one
    column's operand, at, size, that column's piece)."""
    monkeypatch.setattr(fetch, "SLICE_MAX", 0)
    monkeypatch.setattr(fetch, "PIECES", SIZES)
    monkeypatch.setattr(fetch, "PIECE_WORTH", 8)
    monkeypatch.setattr(fetch, "SPEC_SLOTS", 8)
    monkeypatch.setattr(fetch, "SPEC_ROWS", 32)
    cut, real = [], fetch._piece

    def piece(cap, at, size):
        out = real(cap, at, size)
        n = next(iter(cap))
        cut.append((cap[n], tuple(int(x) for x in at), size, out[n]))
        return out
    monkeypatch.setattr(fetch, "_piece", piece)
    return cut


@pytest.fixture()
def fetched(monkeypatch):
    """Every `jax.device_get` of the test, as (what was asked for, bytes
    that came)."""
    calls, real = [], jax.device_get

    def device_get(tree):
        got = real(tree)
        calls.append((tree, sum(a.nbytes for a in jax.tree.leaves(got))))
        return got
    monkeypatch.setattr(jax, "device_get", device_get)
    return calls


# -- the takers on a hand-made capture --------------------------------------


def _capture(P=2, nb=2, W=40):
    """A capture as a traverse program returns it: the identity columns
    lead + (nb, W), a property column its halves lead + (nb, 2, W)."""
    n = P * nb * W
    return {"src": jax.numpy.arange(n, dtype=np.int32).reshape(P, nb, W),
            "eidx": jax.numpy.arange(n, dtype=np.int32).reshape(P, nb, W) * 3,
            "prop:f": jax.numpy.asarray(split_halves(
                (np.arange(n, dtype=np.float64) / 7).reshape(P, nb, W)))}


def _held(cap_dev, rows, kc):
    """Each fetched row is its kept prefix, piece after piece; the
    pieces of a property column are its halves, which join to the
    column's values."""
    for n, col in rows.items():
        want = np.asarray(cap_dev[n])
        for idx in np.ndindex(kc.shape):
            got = col[idx]
            if kc[idx]:
                np.testing.assert_array_equal(
                    np.concatenate(got, axis=-1), want[idx][..., :kc[idx]],
                    str((n, idx)))
            assert sum(a.shape[-1] for a in got) == kc[idx]
        if n.startswith("prop:") and kc.any():
            live = np.arange(want.shape[-1]) < kc[..., None]
            np.testing.assert_array_equal(
                assemble._join_halves(
                    assemble._pieces(list(col.flat)), np.float64)[0],
                (np.arange(want.size // 2, dtype=np.float64) / 7).reshape(
                    live.shape)[live])


@pytest.mark.parametrize("kc,cuts", [
    # nothing kept; 32 in one piece; the whole row of 40 (32, and the
    # last 8 in a piece that starts at W - 8); 5 in one piece of 8
    ([[0, 32], [40, 5]],
     [((0, 1, 0), 32), ((1, 0, 0), 32), ((1, 0, 32), 8), ((1, 1, 0), 8)]),
    # a second piece only where it saves PIECE_WORTH (8) slots: 17 is
    # 16 + 4 (not one of 32), 9 one piece of 16 (8 + 4 would save 4),
    # 24 is 16 + 8, 3 one piece of 4
    ([[3, 17], [9, 24]],
     [((0, 0, 0), 4), ((0, 1, 0), 16), ((0, 1, 16), 4), ((1, 0, 0), 16),
      ((1, 1, 0), 16), ((1, 1, 16), 8)]),
], ids=["empty-exact-whole", "tails"])
def test_pieces_cover_each_row_by_its_own_count(pieces, kc, cuts):
    cap_dev, kc = _capture(), np.asarray(kc)
    take = fetch._taker(cap_dev, {"src", "prop:f"})
    assert isinstance(take, fetch._Pieces) and take.sizes == [4, 8, 16, 32]
    more = take.ask(kc)
    assert [(at, size) for _, at, size, _ in pieces] == cuts
    # the wanted columns are cut together, the others not at all
    assert all(set(p) == {"src", "prop:f"} for p in more)
    take.got(jax.device_get(more))
    rows = take.rows(kc)
    assert set(rows) == {"src", "prop:f"}
    _held(cap_dev, rows, kc)
    slots = sum(size for _, size in cuts)
    assert take.nbytes == slots * (4 + 8) == slots * take.item_bytes()
    assert take.ask(kc) is None                 # nothing is missing now


def test_a_piece_past_the_end_is_clamped_and_trimmed(pieces):
    """`lax.dynamic_slice` moves a start past W - size back to it: the
    host skips what the piece repeats."""
    cap_dev, kc = _capture(W=21), np.asarray([[21, 0], [0, 20]])
    take = fetch._taker(cap_dev)
    take.got(jax.device_get(take.ask(kc)))
    # 21 = 16 + a piece of 8 that would end at 24: cut from 13
    assert [(at, size) for _, at, size, _ in pieces][:2] == \
        [((0, 0, 0), 16), ((0, 0, 13), 8)]
    _held(cap_dev, take.rows(kc), kc)


def test_a_speculation_is_one_small_piece_a_short_row_and_the_rest_a_tail(pieces):
    cap_dev = _capture()
    last, kc = np.asarray([[0, 20], [40, 3]]), np.asarray([[9, 40], [20, 2]])
    take = fetch._taker(cap_dev)
    first = take.speculate(last)
    # one piece a row that last kept something, of no more than
    # SPEC_SLOTS (8) however much that was (20 -> 8), the smallest size
    # that holds less (3 -> 4); the row that kept more than SPEC_ROWS
    # (40 > 32) is not guessed at
    assert [(at, size) for _, at, size, _ in pieces] == \
        [((0, 1, 0), 8), ((1, 1, 0), 4)]
    take.got(jax.device_get(first))
    del pieces[:]
    tail = take.ask(kc)
    # row (0, 0) had nothing and needs 9 (one piece of 16); row (0, 1)
    # had 8 of its 40 (the other 32 in one piece, to the end); row
    # (1, 0) all of its 20 (16 + 4); row (1, 1) lacks nothing
    assert [(at, size) for _, at, size, _ in pieces] == \
        [((0, 0, 0), 16), ((0, 1, 8), 32), ((1, 0, 0), 16), ((1, 0, 16), 4)]
    take.got(jax.device_get(tail))
    _held(cap_dev, take.rows(kc), kc)


def test_heads_slice_every_row_at_one_power_of_two():
    cap_dev, kc = _capture(W=1 << 10), np.asarray([[0, 300], [129, 5]])
    take = fetch._taker(cap_dev, {"src", "prop:f"})
    assert type(take) is fetch._Heads
    assert take.speculate(np.asarray(100)).keys() == {"src", "prop:f"}
    assert take.k == fetch.SLICE_MIN          # the floor
    got = take.ask(kc)
    assert {n: v.shape for n, v in got.items()} == {
        "src": (2, 2, 512), "prop:f": (2, 2, 2, 512)}
    take.got(jax.device_get(got))
    _held(cap_dev, take.rows(kc), kc)
    assert take.ask(kc) is None and take.nbytes == 2 * 2 * 512 * (4 + 8)


# -- through the runtime: rows as the single-slice path's -------------------


def _delta_flags(on):
    cfg = get_config()
    if on:
        cfg.set_dynamic_many({"tpu_delta_max_edges": 64,
                              "tpu_delta_compact_watermark": 2.0})
    else:
        with cfg.lock:
            for k in ("tpu_delta_max_edges", "tpu_delta_compact_watermark"):
                cfg.dynamic_layer.pop(k, None)


MATCH_Q = "MATCH (a:person)-[e:knows*1..2]->(b) WHERE id(a) == 7 RETURN count(*)"
SUBGRAPH_Q = "GET SUBGRAPH 2 STEPS FROM 7 YIELD VERTICES AS nodes"


@pytest.mark.parametrize("case,parts,q", [
    ("go", 1, GO_Q), ("go", 2, GO_Q),
    ("frames", 1, MATCH_Q), ("frames", 2, MATCH_Q), ("frames", 1, SUBGRAPH_Q),
    ("delta", 1, GO_Q), ("delta", 2, GO_Q),
], ids=["go-one-chip", "go-two-shards", "match-frames", "match-frames-two-shards",
        "subgraph-frames", "delta-one-chip", "delta-two-shards"])
def test_rows_equal_the_single_slice_paths(pieces, monkeypatch, case, parts, q):
    """GO (a filtered hop: compacted prefixes), `capture_hops` frames
    and a live delta view (rows EB + Dcap wide, not a multiple of a
    piece), on one chip and on the sharded program."""
    _delta_flags(case == "delta")
    try:
        st = _hubby_store() if case == "frames" and parts == 1 else store_p(parts)
        eng = QueryEngine(st, tpu_runtime=TpuRuntime(make_mesh(parts)))
        assert _rows(eng, q) == _rows(QueryEngine(st), q)       # cold: two-phase
        if case == "delta":
            pins = stats().snapshot().get("tpu_pins", 0)
            for v in (1, 2, 3):
                st.insert_edge("g", v, "knows", 40 + v, 0,
                               {"w": 60, "f": 0.5, "tag": "ann"})
            src, _, rank, dst, _, _ = next(iter(
                st.get_neighbors("g", [1], ["knows"], "out")))
            st.delete_edge("g", src, "knows", dst, rank)
        del pieces[:]
        want = _rows(QueryEngine(st), q)
        assert _rows(eng, q) == want                            # a warm program
        assert pieces, "no piece was cut"
        if case == "delta":
            assert stats().snapshot().get("tpu_pins", 0) == pins
            assert any(v.shape[-1] & (v.shape[-1] - 1) for v, *_ in pieces), \
                "no capture was EB + Dcap wide"
        # the single-slice path on the same store
        monkeypatch.undo()
        assert fetch.SLICE_MAX == 1 << 16
        eng = QueryEngine(st, tpu_runtime=TpuRuntime(make_mesh(parts)))
        assert _rows(eng, q) == want
    finally:
        _delta_flags(False)


def test_pieces_are_cut_on_the_shard_that_holds_the_row(pieces):
    st = store_p(2)
    rt = TpuRuntime(make_mesh(2))
    rows, ts = rt.traverse(st, "g", [1, 2, 3, 4, 5, 6], ["knows"], "out", 2)
    assert rows and pieces
    seen = set()
    for v, at, size, out in pieces:
        # the operand is ONE shard's rows, the piece stays on its device
        assert v.shape[0] == 1 and at[0] == 0
        assert len(v.devices()) == 1 and out.devices() == v.devices()
        seen |= v.devices()
    assert seen == set(rt.mesh.devices.flat)
    assert ts.fetch_bytes_kept == len(rows) * sum(
        {"src": 4, "dst": 4, "rank": 4, "eidx": 4}.values())


@pytest.mark.parametrize("path", ["pieces", "slices"])
def test_a_statement_after_a_smaller_one_of_its_program(path, fetched, request, monkeypatch):
    """`tpu_fetch_bytes` is every byte `device_get` brought, and
    `tpu_fetch_bytes_kept` the kept entries of the columns read.  The
    statement after a smaller one of the same program undershoots the
    speculation, and one refetch is counted: of a wide capture only the
    pieces past the speculated one come in the second `device_get`, of
    a narrow one the exact slice."""
    if path == "pieces":
        request.getfixturevalue("pieces")
        monkeypatch.setattr(fetch, "SPEC_SLOTS", 16)  # the small statement's 16 rows
    else:
        monkeypatch.setattr(fetch, "SLICE_MIN", 1)
    st = store_p(1)
    rt = TpuRuntime(make_mesh(1))

    def run(vids):
        s0, n0 = stats().snapshot(), len(fetched)
        rows, ts = rt.traverse(st, "g", vids, ["knows"], "out", 2)
        s1 = stats().snapshot()
        moved = {k: s1.get(k, 0) - s0.get(k, 0)
                 for k in ("tpu_fetch_bytes", "tpu_fetch_bytes_kept",
                           "tpu_refetches")}
        assert moved["tpu_fetch_bytes"] == sum(b for _, b in fetched[n0:]) \
            == ts.fetch_bytes
        assert moved["tpu_fetch_bytes_kept"] == ts.fetch_bytes_kept \
            == len(rows) * 16
        return rows, moved, fetched[n0:]
    small, big = [1], list(range(2, 6))
    _, moved, calls = run(small)
    assert len(calls) == 2 and moved["tpu_refetches"] == 0      # cold: meta, then rows
    _, moved, calls = run(small)
    assert len(calls) == 1 and moved["tpu_refetches"] == 0      # warm: one round trip
    warm_bytes = moved["tpu_fetch_bytes"]
    rows, moved, calls = run(big)
    assert len(calls) == 2 and moved["tpu_refetches"] == 1
    (_, first_bytes), (second, second_bytes) = calls
    kept, leaves = moved["tpu_fetch_bytes_kept"], jax.tree.leaves(second)
    assert first_bytes == warm_bytes            # what the small statement needed
    if path == "pieces":
        # flat pieces alone (a property column's halves side by side),
        # and with the speculated piece no more than the rows kept and
        # the last piece's slack (one part, one block: one row)
        assert all(a.shape in [(c,) for c in SIZES] + [(2, c) for c in SIZES]
                   for a in leaves)
        assert second_bytes < kept < moved["tpu_fetch_bytes"] \
            < first_bytes + kept + 16 * max(a.shape[-1] for a in leaves)
    else:
        assert all(a.ndim == 3 for a in leaves) and second_bytes >= kept
    assert sorted(map(repr, rows)) == sorted(map(repr, TpuRuntime(
        make_mesh(1)).traverse(st, "g", big, ["knows"], "out", 2)[0]))


def test_an_overflowed_rung_returns_meta_alone(pieces, monkeypatch):
    """The rung that overflows ships its meta and the small piece that
    was speculated for it, dropped: its capture never comes."""
    rungs, real = [], Fetcher.fetch

    def spy(self, res, key, fetch_keys, info):
        before = info["fetch_bytes_kept"]
        host, held = real(self, res, key, fetch_keys, info)
        rungs.append((bool(host["ovf_expand"].any()), "cap" in host,
                      info["fetch_bytes_kept"] - before))
        return host, held
    monkeypatch.setattr(Fetcher, "fetch", spy)
    st = _hubby_store()
    rt = TpuRuntime(make_mesh(1))
    rt.init_eb = 16
    rows, ts = rt.traverse(st, "g", [1], ["knows"], "out", 1)   # fits 16 slots
    assert rows and rungs == [(False, True, len(rows) * 16)]
    del rungs[:], pieces[:]
    # the same program (one seed, one hop) from the hub overflows its
    # rung and climbs
    rows, ts = rt.traverse(st, "g", [7], ["knows"], "out", 1)
    assert len(rows) > 16 and ts.retries >= 1
    assert rungs[0] == (True, False, 0) and rungs[-1] == (False, True, len(rows) * 16)
    assert pieces and ts.fetch_bytes > ts.fetch_bytes_kept == len(rows) * 16


def test_a_lane_views_a_solo_shaped_capture(pieces, clean, company):
    """The lane-batched launch: ONE fetch of lane-major rows, of which
    each member takes its lane (`v[tk.lane]`) and assembles the rows
    its solo run returns."""
    eng = device_engine(TpuRuntime(make_mesh(1)))
    seeds = [1, 2, 3, 5]
    stmts = {sd: GO_TMPL.format(seed=sd) for sd in seeds}
    truth = {}
    for sd in seeds:
        out = {}
        _run_stmt(eng, stmts[sd], out, sd, [])
        truth[sd] = sorted(map(repr, out[sd][0].data.rows))
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 300_000})
    del pieces[:]

    def one_launch_of_all_four():
        """A round in which every statement joined the ONE launch.  On a
        loaded machine a thread can start after the forming window has
        closed and run solo (right rows, a solo-shaped capture): that
        round is not the case under test, and is run again."""
        for _ in range(5):
            n0, s0 = len(pieces), stats().snapshot()
            out = _concurrent(eng, stmts)
            s1 = stats().snapshot()
            for sd in seeds:
                assert sorted(map(repr, out[sd][0].data.rows)) == truth[sd]
            formed, lanes = (s1.get(k, 0) - s0.get(k, 0) for k in (
                "tpu_batches_formed", "tpu_batch_lanes.sum"))
            if (formed, lanes) == (1, len(seeds)):
                return
            del pieces[n0:]
        pytest.fail("the four statements never met in one forming window")
    for _ in range(2):                          # cold, then warm
        one_launch_of_all_four()
    # lane-major operands: (L, P, nb, W), a piece names lane, part, block
    assert pieces and all(v.ndim == 4 and len(at) == 4 for v, at, *_ in pieces)


# -- fault (b): no fetch program is first met through a kept size ------------


def _degrees_store(degs, n=400):
    from nebula_tpu.graphstore.schema import PropDef, PropType
    from nebula_tpu.graphstore.store import GraphStore
    st = GraphStore()
    st.create_space("dg", partition_num=4, vid_type="INT64")
    st.catalog.create_tag("dg", "P", [PropDef("x", PropType.INT64)])
    st.catalog.create_edge("dg", "E", [PropDef("w", PropType.INT64),
                                       PropDef("f", PropType.DOUBLE)])
    for v in range(n):
        st.insert_vertex("dg", v, "P", {"x": v})
    for v, d in enumerate(degs):
        for i in range(d):
            st.insert_edge("dg", v, "E", (v + 1 + i) % n, 0, {"w": i, "f": i / 3})
    return st


@pytest.mark.parametrize("path", ["slices", "pieces"])
def test_a_sweep_of_kept_sizes_compiles_nothing(path, monkeypatch):
    """After a traverse program's first build, statements of that
    program whose kept sizes cross several powers of two compile
    nothing more (backend-compile events, as the benchmark's
    `xla.compiles_in_window` counts them): the slices and the pieces
    were compiled with the program."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.lib.compiles import CompileWatch
    if path == "pieces":
        monkeypatch.setattr(fetch, "SLICE_MAX", 0)
        monkeypatch.setattr(fetch, "PIECES", SIZES)
    else:
        monkeypatch.setattr(fetch, "SLICE_MIN", 1)
    degs = [1, 3, 9, 20, 50, 120, 300]
    st = _degrees_store(degs)
    rt = TpuRuntime(make_mesh(1))
    watch = CompileWatch()

    def go(v):
        rows, ts = rt.traverse(st, "dg", [v], ["E"], "out", 1)
        assert len(rows) == degs[v] and ts.retries == 0
        return ts
    go(0)                                       # pins, builds, compiles
    assert watch.compiles > 0
    # ascending (every statement undershoots the last one's speculation)
    # and back down
    built, kept = watch.compiles, set()
    for v in list(range(1, len(degs))) + list(range(len(degs) - 2, -1, -1)):
        kept.add(pow2(go(v).fetch_bytes_kept))
    assert len(kept) >= 5 and len(rt._fns) == 1
    assert watch.compiles == built


@pytest.mark.parametrize("kernel", ["traverse", "hops", "bfs"])
def test_the_fetched_leaves_are_the_named_list(kernel, fetched, monkeypatch):
    """`frontier` and `fcount` stay on the device: the program writes
    them, no caller reads them, and the four-chip cell's `frontier` was
    more bytes than its rows."""
    on_device, real = [], Fetcher.fetch

    def spy(self, res, *a):
        on_device.append(set(res))
        return real(self, res, *a)
    monkeypatch.setattr(Fetcher, "fetch", spy)
    st = store_p(2)
    rt = TpuRuntime(make_mesh(2))
    if kernel == "traverse":
        rt.traverse(st, "g", [1, 2], ["knows"], "out", 2)
    elif kernel == "hops":
        rt.traverse_hops(st, "g", [1, 2], ["knows"], "out", 2)
    else:
        rt.bfs(st, "g", [1, 2], ["knows"], "out", 3)
    engaged = set(fetch._ENGAGEMENT)
    # a BFS's level loops say their trips too (PR 42); it lays no member-plan count
    want = {"dist", "hop_edges", "ovf_expand", "bottom_up", "chunks_run", "chunks_budget"} \
        if kernel == "bfs" else {"hop_edges", "ovf_expand", "kcount", "frontier_sizes"} | engaged
    metas = [set(tree[0]) for tree, _ in fetched if isinstance(tree, tuple)]
    assert metas and all(m == want for m in metas), metas
    assert want <= set(fetch._FETCHED)
    if kernel != "bfs":
        assert all({"frontier", "fcount", "cap"} <= leaves for leaves in on_device)
    for tree, _ in fetched:
        for path, _leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            assert not {"frontier", "fcount"} & {
                getattr(k, "key", None) for k in path}
