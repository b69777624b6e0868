"""Edge property columns live on the device as their two 32-bit halves
(device.py `split_halves`, PR 35): a program's operands hold no 64-bit
array, a gather reads both halves by one index array (hop.py
`take_halves`), a predicate's 64-bit value is rebuilt per gathered slot
(`join_halves`), a carried column stays its halves through the capture
and the fetch and is joined on the host as its pieces are concatenated
(assemble.py `_join_halves`).

Rows have to be the host engine's to the bit, whatever the values: the
store here holds the ones a half could lose (an integer that needs the
high half, negative ones, the NULL sentinel `INT_NULL` next to its
neighbour `INT_NULL + 1`, NaN padding next to values, a double beyond
float32's range, a denormal, the last bit of a mantissa, -0.0, an
infinity).
"""
import threading
import traceback
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from nebula_tpu.core.value import NULL
from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.core import expr as E
from nebula_tpu.graphstore.csr import (INT_NULL, decode_prop_column,
                                       decode_prop_column_np)
from nebula_tpu.graphstore.schema import PropDef, PropType
from nebula_tpu.graphstore.store import GraphStore
from nebula_tpu.utils.config import get_config
from nebula_tpu.utils.stats import stats

tpu = pytest.importorskip("nebula_tpu.tpu")
from nebula_tpu.tpu import (TpuRuntime, assemble, make_mesh,  # noqa: E402
                            runtime)
from nebula_tpu.tpu.device import (join_halves, nan_halves,   # noqa: E402
                                   split_halves)

from test_batch import clean, company                        # noqa: E402,F401
from test_fetch_pieces import pieces                         # noqa: E402,F401
from test_hop_by_need import _rows as _rows_in               # noqa: E402

N = 48
WS = [NULL, INT_NULL + 1, -1, -(2 ** 40) - 3, 2 ** 40 + 5, 2 ** 63 - 1, 0,
      7, 2 ** 32, 2 ** 31, -(2 ** 31), 41]
FS = [NULL, 1e300, -1e300, 5e-324, -0.0, 0.1, 1.0 + 2.0 ** -52,
      3.4028236e38, float("inf"), 0.75, -2.5e-310, 1 / 3]


def halves_store(parts, ws=WS, fs=FS):
    """Vertex v knows v+1 .. v+5 (mod N); edge number i carries WS[i %
    12] and FS[(i // 5) % 12], so that every pairing of neighbours comes
    up and every CSR row mixes NULLs with values."""
    st = GraphStore()
    st.create_space("h", partition_num=parts, vid_type="INT64")
    st.catalog.create_tag("h", "person", [PropDef("age", PropType.INT64)])
    st.catalog.create_edge("h", "knows", [PropDef("w", PropType.INT64),
                                          PropDef("f", PropType.DOUBLE)])
    for v in range(N):
        st.insert_vertex("h", v, "person", {"age": v})
    i = 0
    for v in range(N):
        for k in range(1, 6):
            st.insert_edge("h", v, "knows", (v + k) % N, 0,
                           {"w": ws[i % len(ws)],
                            "f": fs[(i // 5) % len(fs)]})
            i += 1
    return st


def _rows(eng, q):
    return _rows_in(eng, q, "h")


def _same_as_host(st, rt, qs):
    """Every statement returns the host engine's rows, from the device
    (one launch or more a statement, no fallback)."""
    dev_eng, host_eng = QueryEngine(st, tpu_runtime=rt), QueryEngine(st)
    for q in qs:
        s0 = stats().snapshot()
        got, want = _rows(dev_eng, q), _rows(host_eng, q)
        s1 = stats().snapshot()
        assert got == want, q
        assert want, "nothing to compare: " + q
        assert s1.get("tpu_kernel_runs", 0) > s0.get("tpu_kernel_runs", 0), q
        assert not any(k.startswith("tpu_host_fallback") and
                       s1[k] != s0.get(k, 0) for k in s1), q


FROM = "GO 2 STEPS FROM 0, 1, 2, 3, 17 OVER knows "
USES = {
    "carry-int": [FROM + "YIELD dst(edge), knows.w"],
    "carry-double": [FROM + "YIELD knows.f, dst(edge)"],
    "carry-both": [FROM + "YIELD knows.w, knows.f, src(edge)"],
    "filter-int": [
        FROM + "WHERE knows.w < 0 YIELD src(edge), dst(edge)",
        # needs the high half; and the sentinel's neighbour is a value
        FROM + "WHERE knows.w > 1099511627776 YIELD src(edge), dst(edge)",
        FROM + f"WHERE knows.w == {INT_NULL + 1} YIELD src(edge), dst(edge)",
        FROM + "WHERE knows.w IS NOT NULL AND knows.w % 2 == 1 "
               "YIELD src(edge), dst(edge)"],
    "filter-double": [
        FROM + "WHERE knows.f > 1e30 YIELD src(edge), dst(edge)",
        FROM + "WHERE knows.f < 0.5 YIELD src(edge), dst(edge)",
        FROM + "WHERE knows.f > 1.0 AND knows.f < 1.0000000000000004 "
               "YIELD src(edge), dst(edge)"],
    "filter-and-carry-both": [
        FROM + "WHERE knows.w < 0 OR knows.f > 1e30 "
               "YIELD knows.w, knows.f, dst(edge)",
        FROM + "WHERE knows.f < 0.5 AND knows.w > 5 "
               "YIELD knows.f, knows.w"],
}


@pytest.mark.parametrize("taker", ["heads", "pieces"])
@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
@pytest.mark.parametrize("use", sorted(USES))
def test_rows_are_the_host_engines(use, parts, taker, request):
    """A carried and a filtered int64 and float64 column, and both at
    once, through the narrow capture's slices (`_Heads`) and the wide
    one's pieces (`_Pieces`), on one chip and on the sharded program."""
    if taker == "pieces":
        cut = request.getfixturevalue("pieces")
    _same_as_host(halves_store(parts), TpuRuntime(make_mesh(parts)),
                  USES[use])
    if taker == "pieces":
        assert cut, "no piece was cut"


@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
def test_a_live_delta_row_and_a_tombstone(parts):
    """The delta column's halves (`d_props`, hop.py `_gather_merged`
    and `_live_rows`): rows written after the pin are carried and
    filtered beside the base rows, a deleted one is gone, nothing
    re-pins."""
    cfg = get_config()
    cfg.set_dynamic_many({"tpu_delta_max_edges": 64,
                          "tpu_delta_compact_watermark": 2.0})
    try:
        st = halves_store(parts)
        rt = TpuRuntime(make_mesh(parts))
        qs = USES["carry-both"] + USES["filter-and-carry-both"]
        _same_as_host(st, rt, qs)
        pins = stats().snapshot().get("tpu_pins", 0)
        for k, (w, f) in enumerate([(INT_NULL + 1, 1e300), (NULL, NULL),
                                    (-(2 ** 40) - 3, 5e-324), (9, -0.0)]):
            st.insert_edge("h", 1 + k, "knows", 30 + k, 1, {"w": w, "f": f})
        st.delete_edge("h", 2, "knows", 3, 0)
        _same_as_host(st, rt, qs)
        assert stats().snapshot().get("tpu_pins", 0) == pins, \
            "the writes re-pinned: the delta plane was not exercised"
        dd = rt.snapshots["h"].delta
        assert any(any(e["rows"]) for e in dd.blocks.values())
    finally:
        with cfg.lock:
            for k in ("tpu_delta_max_edges", "tpu_delta_compact_watermark"):
                cfg.dynamic_layer.pop(k, None)


def test_a_lane_batched_launch(clean, company):                # noqa: F811
    """Statements that share one launch each get their own lane's
    halves (`v[tk.lane]` of lane-major rows)."""
    st = halves_store(1)
    eng = QueryEngine(st, tpu_runtime=TpuRuntime(make_mesh(1)))
    tmpl = ("GO 2 STEPS FROM {seed} OVER knows WHERE knows.w != 7 "
            "YIELD knows.w, knows.f, dst(edge)")
    seeds = [0, 5, 11, 23]
    host = QueryEngine(st)
    truth = {sd: _rows(host, tmpl.format(seed=sd)) for sd in seeds}
    assert _rows(eng, tmpl.format(seed=seeds[0])) == truth[seeds[0]]  # pinned
    get_config().set_dynamic_many({"batch_max_lanes": 8,
                                   "batch_wait_us": 2_000_000})
    s0 = stats().snapshot()
    out, errs = {}, []

    def run(sd):
        try:
            out[sd] = _rows(eng, tmpl.format(seed=sd))
        except Exception:  # noqa: BLE001 — reported below
            errs.append(traceback.format_exc())
    threads = [threading.Thread(target=run, args=(sd,), daemon=True)
               for sd in seeds]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs and not any(t.is_alive() for t in threads), errs[:2]
    s1 = stats().snapshot()
    assert s1.get("tpu_batches_formed", 0) - s0.get("tpu_batches_formed", 0) == 1
    assert out == truth


@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
@pytest.mark.parametrize("where", [
    "knows.w < 0", "knows.f > 1e30 OR knows.w == 41",
    "knows.w > 1099511627776 OR knows.f < 0.2"])
def test_a_bfs_level_with_an_edge_predicate(parts, where):
    """FIND SHORTEST PATH WHERE <pred>: the level bodies'
    `algo/frontier.py` `_keep` gathers the column's halves (top-down and
    bottom-up on one chip, the shard_map level on two)."""
    st = halves_store(parts)
    q = (f"FIND SHORTEST PATH FROM 0, 4 TO 29, 40 OVER knows WHERE {where} "
         f"UPTO 12 STEPS YIELD path AS p")
    s0 = stats().snapshot().get("tpu_kernel_runs", 0)
    got = _rows(QueryEngine(st, tpu_runtime=TpuRuntime(make_mesh(parts))), q)
    assert stats().snapshot().get("tpu_kernel_runs", 0) > s0
    assert got == _rows(QueryEngine(st), q) and got


@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
def test_no_property_leaf_on_the_device_is_64_bit(parts):
    """After a pin and after a delta apply every leaf of
    `DeviceBlock.props` and `d_props` is a column's `uint32` halves
    `(P, 2, width)`, the bytes resident are what the 64-bit columns
    took, and a traverse program's operands hold no 64-bit leaf
    (`tpu_wide_operand_bytes`)."""
    cfg = get_config()
    cfg.set_dynamic_many({"tpu_delta_max_edges": 64,
                          "tpu_delta_compact_watermark": 2.0})
    try:
        st = halves_store(parts)
        rt = TpuRuntime(make_mesh(parts))
        dev = rt.pin(st, "h")

        def check():
            for bk, b in dev.blocks.items():
                hb = dev.host.blocks[bk]
                for n, a in b.props.items():
                    assert a.dtype == np.uint32, (bk, n)
                    assert a.shape == (parts, 2, hb.props[n].shape[1])
                    assert hb.props[n].dtype.itemsize == 8  # the host's stay
                    np.testing.assert_array_equal(
                        np.asarray(a), split_halves(hb.props[n]))
            for bk, e in dev.delta.blocks.items():
                for n, a in e["d_props"].items():
                    assert a.dtype == np.uint32, (bk, n)
                    assert a.shape == (parts, 2, dev.delta.host.dcap)
                    np.testing.assert_array_equal(
                        np.asarray(a), split_halves(e["np"]["d_props"][n]))
            assert dev.hbm_bytes() == dev.host.hbm_bytes() \
                + dev.delta.host.nbytes()
            assert sum(dev.shard_hbm_bytes().values()) == dev.hbm_bytes()
        check()
        st.insert_edge("h", 1, "knows", 30, 1, {"w": -5, "f": 1e300})
        assert rt.pin(st, "h") is dev and any(
            any(e["rows"]) for e in dev.delta.blocks.values())
        check()
        s0 = stats().snapshot()
        rows, _ = rt.traverse(st, "h", [0, 1], ["knows"], "out", 2)
        s1 = stats().snapshot()
        assert rows
        assert s1["tpu_wide_operand_bytes.count"] \
            - s0.get("tpu_wide_operand_bytes.count", 0) == 1
        assert s1["tpu_wide_operand_bytes.sum"] \
            == s0.get("tpu_wide_operand_bytes.sum", 0)
    finally:
        with cfg.lock:
            for k in ("tpu_delta_max_edges", "tpu_delta_compact_watermark"):
                cfg.dynamic_layer.pop(k, None)


def test_a_64_bit_operand_is_counted(monkeypatch):
    """What the series exists for: a 64-bit leaf among a traverse
    program's operands is counted at its bytes, once a launch."""
    st = halves_store(1)
    rt = TpuRuntime(make_mesh(1))
    real = rt._block_leaves
    extra = np.zeros((1, 1000), np.int64)

    def leaves(dev, block_keys, prop_names):
        view, blocks = real(dev, block_keys, prop_names)
        blocks[0] = dict(blocks[0], stowaway=extra)
        return view, blocks
    monkeypatch.setattr(rt, "_block_leaves", leaves)
    s0 = stats().snapshot()
    rows, _ = rt.traverse(st, "h", [0], ["knows"], "out", 1)
    s1 = stats().snapshot()
    assert rows
    assert s1["tpu_wide_operand_bytes.sum"] \
        - s0.get("tpu_wide_operand_bytes.sum", 0) == extra.nbytes


@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_halves_join_to_the_bit(dtype):
    """`split_halves` then the host's join (pieces of uneven sizes, a
    permutation a row) give the column back bit for bit, and so does
    the program's `join_halves` of an integer."""
    vals = [v for v in (WS if dtype == np.int64 else FS) if v is not NULL]
    col = np.asarray(vals * 4, dtype).reshape(2, -1)
    col[0, 0] = INT_NULL if dtype == np.int64 else np.nan
    h = split_halves(col)
    assert h.dtype == np.uint32 and h.shape == (2, 2, col.shape[1])
    if dtype == np.int64:
        back = np.asarray(join_halves(h, dtype))
        assert back.dtype == dtype
        np.testing.assert_array_equal(back, col)
    # the fetched form: each row its pieces (2, n) in slot order
    rows = [[h[0][:, :5], h[0][:, 5:]], [h[1]]]
    got, has_null = assemble._join_halves(assemble._pieces(rows), dtype)
    assert has_null is True
    np.testing.assert_array_equal(got.view(np.int64),
                                  col.reshape(-1).view(np.int64))
    pm = np.random.default_rng(3).permutation(col.shape[1])
    got, has_null = assemble._join_halves(
        assemble._pieces(rows, [pm, None]), dtype)
    assert has_null is True
    np.testing.assert_array_equal(
        got.view(np.int64),
        np.concatenate([col[0][pm], col[1]]).view(np.int64))


def test_a_predicates_double_is_the_pair_a_transfer_makes():
    """What a predicate reads for a stored double (device.py
    `join_halves`): float32(x) and the float32 of what that leaves, the
    pair a chip without 64-bit lanes is handed for a `float64` operand,
    worked out of the bit halves in 32-bit arithmetic, the same on every
    backend.  So: the stored double to the bit from 2^-74 up to
    float32's largest; the pair's precision under that; an INFINITY
    beyond float32's range (1e300, and 3.4028236e38, which rounds up out
    of it), never a NaN; zero under float32's smallest normal number;
    and the NULL test is the bits' own."""
    rng = np.random.default_rng(35)
    f = np.concatenate([
        np.asarray([v for v in FS if v is not NULL]),
        [np.nan, -np.inf, 3.4028234e38, 3.4028235677973366e38, 1.7e308,
         2.0 ** -126, 2.0 ** -126 * (1 - 2.0 ** -30), 2.0 ** -149,
         1 - 2.0 ** -53, 16777215.5, 1.0 + 2.0 ** -24, 1.0 + 3 * 2.0 ** -24,
         2.0 ** -74, 1e-40, -1e-40],
        rng.random(4000) * 100, rng.standard_normal(4000) * 1e6,
        np.exp(rng.uniform(-80, 80, 4000)) * rng.choice([-1, 1], 4000),
        rng.integers(0, 2 ** 64, 4000, dtype=np.uint64).view(np.float64)])
    pair = split_halves(f)
    v = np.asarray(jax.jit(lambda p: join_halves(p, np.float64))(pair))
    assert v.dtype == np.float64
    np.testing.assert_array_equal(np.asarray(nan_halves(pair)), np.isnan(f))
    with np.errstate(all="ignore"):
        hi = f.astype(np.float32)
        lo = (f - hi.astype(np.float64)).astype(np.float32)
    a = np.abs(f)
    inside = np.isfinite(hi) & (a >= 2.0 ** -126)
    np.testing.assert_array_equal(v[inside].astype(np.float32), hi[inside])
    exact = inside & (a >= 2.0 ** -74)
    np.testing.assert_array_equal(v[exact], f[exact])
    small = inside & ~exact         # the low float32 goes under the range
    pair_gap = np.abs(hi[small].astype(np.float64) + lo[small] - f[small])
    assert (np.abs(v[small] - f[small])
            <= pair_gap + a[small] * 2.0 ** -24).all()
    over = ~np.isnan(f) & ~np.isfinite(hi)
    assert over.sum() > 100
    np.testing.assert_array_equal(v[over], np.sign(f[over]) * np.inf)
    under = a < 2.0 ** -126 * (1 - 2.0 ** -25)
    assert under.sum() > 100 and (v[under] == 0).all()
    assert np.isnan(v[np.isnan(f)]).all()


# -- a fetched column becomes its decoded host column in one pass (PR 40) --

def _column(dtype, n, nulls):
    """A column of `n` values that hold no sentinel, then the kind's
    NULL sentinel at `nulls`."""
    col = np.random.default_rng(n).integers(-2 ** 40, 2 ** 40, n).astype(dtype)
    col[list(nulls)] = INT_NULL if dtype == np.int64 else np.nan
    return col


def _scan(col):
    """The decode's question, as the parent asked it of a whole column."""
    return bool(np.isnan(col).any() if col.dtype == np.float64
                else (col == INT_NULL).any())


def _decode_as_the_parent_did(pt, raw, pool):
    """graphstore/csr.py `decode_prop_column_np` of the numeric kinds at
    b26b7fb: a copy, a scan through a temporary, the object array."""
    a = raw.astype(np.float64 if pt == PropType.DOUBLE else np.int64)
    if not _scan(a):
        return a
    out = np.empty(len(raw), dtype=object)
    out[:] = decode_prop_column(pt, raw, pool)
    return out


CUTS = (5, 22)                  # three uneven pieces of a column of 23
WHERE = {"none": [], "first": [0], "last": [22], "before-a-boundary": [4],
         "after-a-boundary": [5], "both-sides": [21, 22], "all": range(23)}


@pytest.mark.parametrize("lib", ["native", "numpy"])
@pytest.mark.parametrize("where", sorted(WHERE))
@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_the_join_answers_the_decode_and_the_decode_copies_nothing(
        dtype, where, lib, monkeypatch):
    """`_join_halves` over a column's pieces: the column bit for bit
    and the answer the decode would have scanned for, wherever the NULL
    sits among the pieces.  On that answer the decode of a null-free
    column IS the joined array; one with a NULL is the parent's object
    array element for element; and without an answer the decode scans,
    and still hands back a null-free array of its own dtype uncopied."""
    from nebula_tpu.native import kernels
    if lib == "numpy":
        monkeypatch.setattr(kernels, "get_lib", lambda: None)
    pt = PropType.INT64 if dtype == np.int64 else PropType.DOUBLE
    col = _column(dtype, 23, WHERE[where])
    parts = np.split(split_halves(col), CUTS, axis=-1)
    got, has_null = assemble._join_halves(parts, dtype)
    assert got.dtype == dtype and got.flags.owndata
    np.testing.assert_array_equal(got.view(np.int64), col.view(np.int64))
    assert has_null is _scan(col) is (where != "none")
    want = _decode_as_the_parent_did(pt, col, None)
    for answer in (has_null, None):
        dec = decode_prop_column_np(pt, got, None, answer)
        assert dec.dtype == want.dtype
        if where == "none":
            assert dec is got and np.shares_memory(dec, got)
            np.testing.assert_array_equal(dec.view(np.int64),
                                          want.view(np.int64))
        else:
            assert dec.dtype == object and not np.shares_memory(dec, got)
            assert [repr(x) for x in dec] == [repr(x) for x in want]
            assert [x is NULL for x in dec] == [i in WHERE[where]
                                                for i in range(23)]
    # a column of another dtype than its host kind's is still converted
    if dtype == np.int64 and where == "none":
        narrow = col.astype(np.int32)
        dec = decode_prop_column_np(pt, narrow, None)
        assert dec.dtype == np.int64 and not np.shares_memory(dec, narrow)


@pytest.fixture
def pool():
    own = None
    p = runtime._assembly_pool()
    if p is None:                       # a host with one core makes none
        p = own = ThreadPoolExecutor(2)
    yield p
    if own is not None:
        own.shutdown()


def _serial(columns):
    return [assemble._join_halves(parts, dt) if parts[0].ndim == 2
            else (assemble._cat_parts(parts, dt), False)
            for parts, dt in columns]


LAYOUTS = {
    "one-piece": [[7]], "uneven": [[1, 30, 2, 11]],
    "an-empty-part": [[4, 0, 9], [0, 5]],
    "three-columns-of-eight": [[3, 1, 4, 1, 5, 9, 2, 6]] * 3,
    "24-tasks": [[2] * 8, [5] * 8, [1, 2, 3, 4, 5, 6, 7, 8]],
    **{f"{k}-tasks": [[3] * k] for k in (2, 5, 13)},
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_side_by_side_is_the_serial_assembly(layout, pool):
    """1 to 24 piece-passes handed to the pool fill the columns the
    serial passes fill, to the bit, with the same NULL answers: an
    identity column widened, an int64 and a float64 property column
    joined (a NULL in the last piece of each)."""
    kinds = [(np.int32, np.int64), (np.int64, np.int64),
             (np.float64, np.float64)]
    columns = []
    for i, sizes in enumerate(LAYOUTS[layout]):
        src, dt = kinds[i % 3]
        n = sum(sizes)
        if src == np.int32:
            col = np.arange(n, dtype=np.int32) - 3
            whole = col
        else:
            col = _column(src, n, [n - 1])
            whole = split_halves(col)
        columns.append((np.split(whole, np.cumsum(sizes)[:-1], axis=-1), dt))
    got, want = assemble._cat_side_by_side(pool, columns), _serial(columns)
    assert len(got) == len(want)
    for (g, gn), (w, wn), (parts, dt) in zip(got, want, columns):
        assert g.dtype == w.dtype == dt and g.flags.owndata
        np.testing.assert_array_equal(g.view(np.int64), w.view(np.int64))
        assert gn is wn is (parts[0].ndim == 2)


def test_a_failed_piece_pass_is_the_statements_error(pool, monkeypatch):
    """One pass of many raises: the assembly raises it once the others
    have ended, and the pool assembles the next statement's columns."""
    col = _column(np.int64, 40, [])
    columns = [(np.split(split_halves(col), [9, 20, 33], axis=-1), np.int64)]
    real, seen = assemble.native_join_halves, []

    def faulty(pair, out):
        seen.append(pair.shape[-1])
        if pair.shape[-1] == 11:
            raise MemoryError("a piece-pass failed")
        return real(pair, out)
    monkeypatch.setattr(assemble, "native_join_halves", faulty)
    with pytest.raises(MemoryError, match="a piece-pass failed"):
        assemble._cat_side_by_side(pool, columns)
    assert sorted(seen) == [7, 9, 11, 13]     # every pass ran to its end
    monkeypatch.setattr(assemble, "native_join_halves", real)
    (got, has_null), = assemble._cat_side_by_side(pool, columns)
    np.testing.assert_array_equal(got, col)
    assert has_null is False


def test_many_statements_share_the_pool(pool):
    """More statements than cores hand their pieces to the one pool at
    once: each gets its own columns back, to the bit, and none waits for
    ever (a pass never submits a pass)."""
    import sys
    n_threads, rounds = 16, 40
    cols = [_column(np.float64 if t % 2 else np.int64, 200 + 37 * t, [t])
            for t in range(n_threads)]
    wrong, done = [], []

    def statement(t):
        col = cols[t]
        columns = [(np.split(split_halves(col), [3, 50, 51, 120], axis=-1),
                    col.dtype)] * 2
        for _ in range(rounds):
            for got, has_null in assemble._cat_side_by_side(pool, columns):
                if has_null is not True or not (
                        got.view(np.int64) == col.view(np.int64)).all():
                    wrong.append(t)
        done.append(t)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=statement, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not any(th.is_alive() for th in threads)
    assert sorted(done) == list(range(n_threads)) and not wrong


NO_NULL_WS = [w for w in WS if w is not NULL]
NO_NULL_FS = [f for f in FS if f is not NULL]
YIELDS = [(E.FunctionCall("dst", [E.EdgeExpr()]), "d"),
          (E.EdgeProp("knows", "w"), "w"), (E.EdgeProp("knows", "f"), "f")]
# vertex 12's five edges carry a NULL `f`; it is reached from 8
GO = "GO 2 STEPS FROM 0, 1, 2, 3, 8, 17 OVER knows YIELD dst(edge), knows.w, knows.f"


def _moved(s0, s1, key):
    return s1.get(key, 0) - s0.get(key, 0)


@pytest.mark.parametrize("side_by_side", [False, True],
                         ids=["serial", "side-by-side"])
@pytest.mark.parametrize("parts", [1, 2], ids=["one-chip", "two-shards"])
def test_traverse_with_yields_over_stored_nulls(parts, side_by_side,
                                                monkeypatch):
    """`TpuRuntime.traverse(yields=[dst, w, f])`: over final-hop edges
    that carry a NULL `w` and a NULL `f` the rows are the host
    engine's, `NULL` where stored; the sibling statement over a store
    without NULLs returns columns of their native dtype, and both of its
    property columns took their NULL answer from the assembling pass.
    Under the threshold the pool is never asked for; with the threshold
    at one row every row's pieces go through it, under ONE concat span a
    block where the serial passes open one a column."""
    if side_by_side:
        monkeypatch.setattr(runtime, "POOL_MIN_ROWS", 1)
        if runtime._assembly_pool() is None:
            pytest.skip("one core: no pool is made")
    else:
        def no_pool():
            raise AssertionError("a small statement asked for the pool")
        monkeypatch.setattr(runtime, "_assembly_pool", no_pool)
    for ws, fs, dtypes in ((WS, FS, (np.int64, object, object)),
                           (NO_NULL_WS, NO_NULL_FS,
                            (np.int64, np.int64, np.float64))):
        st = halves_store(parts, ws, fs)
        rt = TpuRuntime(make_mesh(parts))
        s0 = stats().snapshot()
        ds, _ = rt.traverse(st, "h", [0, 1, 2, 3, 8, 17], ["knows"], "out",
                            2, yields=YIELDS)
        s1 = stats().snapshot()
        cols = [ds.column_array(n) for n in "dwf"]
        assert tuple(c.dtype for c in cols) == dtypes
        assert all(c.flags.owndata for c in cols)
        n = len(cols[0])
        assert sorted(map(repr, ds.rows)) == _rows(QueryEngine(st), GO) and n
        assert _moved(s0, s1, "tpu_mat_rows.sum") == n
        assert _moved(s0, s1, "tpu_mat_pooled_rows.sum") == n * side_by_side
        assert _moved(s0, s1, "tpu_mat_numeric_cols.sum") == 2
        assert _moved(s0, s1, "tpu_mat_one_pass_cols.sum") == 2
        # one block: a concat span a column, or one for the pooled block
        assert _moved(s0, s1, "stmt_phase_n{phase=mat_concat}") == \
            (1 if side_by_side else 3)
        assert _moved(s0, s1, "stmt_phase_n{phase=mat_decode}") == 2


def test_a_host_gathered_column_is_scanned_by_the_decode():
    """A property the program does not gather (a fifth yielded column
    falls back to the captured `eidx`) has no assembling pass to answer
    for it: its decode scans, the rows are still the host engine's, and
    it counts against `tpu_mat_one_pass_cols`."""
    st = GraphStore()
    st.create_space("h", partition_num=1, vid_type="INT64")
    st.catalog.create_tag("h", "person", [PropDef("age", PropType.INT64)])
    names = ["a", "b", "c", "d", "e"]
    st.catalog.create_edge("h", "knows",
                           [PropDef(n, PropType.INT64) for n in names])
    for v in range(12):
        st.insert_vertex("h", v, "person", {"age": v})
    for v in range(12):
        for k in (1, 2):
            st.insert_edge("h", v, "knows", (v + k) % 12, 0,
                           {n: (NULL if n == "e" and v == 3 else v * 10 + i)
                            for i, n in enumerate(names)})
    rt = TpuRuntime(make_mesh(1))
    s0 = stats().snapshot()
    ds, _ = rt.traverse(st, "h", [0, 1, 2], ["knows"], "out", 2,
                        yields=[(E.EdgeProp("knows", n), n) for n in names])
    s1 = stats().snapshot()
    q = "GO 2 STEPS FROM 0, 1, 2 OVER knows YIELD " + \
        ", ".join("knows." + n for n in names)
    assert ds.column_array("e").dtype == object     # v == 3 is reached
    assert ds.column_array("a").dtype == np.int64
    assert sorted(map(repr, ds.rows)) == _rows(QueryEngine(st), q)
    assert _moved(s0, s1, "tpu_mat_numeric_cols.sum") == 5
    assert _moved(s0, s1, "tpu_mat_one_pass_cols.sum") == 4
