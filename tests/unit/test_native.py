"""Native (C++) kernel tests: every entry point against its Python/NumPy
fallback — the native path must be a pure speedup, never a semantic
change."""
import ctypes
import random

import numpy as np
import pytest

from nebula_tpu.native import available, get_lib
from nebula_tpu.native.kernels import (build_coo_csr, csv_ingest,
                                       dst_sort_key, fnv1a)

pytestmark = pytest.mark.skipif(not available(),
                                reason="native lib unavailable (no g++?)")


def random_coo(seed, n=500, P=8, nverts=64):
    rng = random.Random(seed)
    src = np.asarray([rng.randrange(nverts) for _ in range(n)], np.int64)
    dst = np.asarray([rng.randrange(nverts) for _ in range(n)], np.int64)
    rank = np.asarray([rng.randrange(3) for _ in range(n)], np.int64)
    vmax = (nverts + P - 1) // P
    return src, dst, rank, vmax


def numpy_reference(src, dst, rank, key, P, vmax):
    """Force the fallback by simulating lib absence via direct call of
    the fallback branch (build_coo_csr falls back only when the native
    call fails, so re-implement the reference ordering here)."""
    n = len(src)
    part = src % P
    local = src // P
    order = np.lexsort((np.arange(n), key, rank, local, part))
    counts = np.bincount(part, minlength=P)
    emax = max(1, int(counts.max()))
    indptr = np.zeros((P, vmax + 1), np.int32)
    nbr = np.full((P, emax), -1, np.int32)
    rk = np.zeros((P, emax), np.int32)
    perm = np.full((P, emax), -1, np.int64)
    pos = np.zeros(P, np.int64)
    for k in order:
        p = int(part[k])
        s = int(pos[p])
        pos[p] += 1
        perm[p, s] = k
        nbr[p, s] = dst[k]
        rk[p, s] = rank[k]
        indptr[p, local[k] + 1] += 1
    np.cumsum(indptr, axis=1, out=indptr)
    return indptr, nbr, rk, perm, emax


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_build_csr_matches_reference(seed):
    src, dst, rank, vmax = random_coo(seed)
    key = dst.copy()
    got = build_coo_csr(src, dst, rank, key, 8, vmax)
    want = numpy_reference(src, dst, rank, key, 8, vmax)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_build_csr_empty():
    indptr, nbr, rk, perm, emax = build_coo_csr(
        np.zeros(0, np.int64), np.zeros(0, np.int64),
        np.zeros(0, np.int64), np.zeros(0, np.int64), 4, 5)
    assert indptr.shape == (4, 6) and emax == 1


def test_dst_sort_key_strings():
    key = dst_sort_key(["bob", "ann", "bob", "cid"])
    assert key.tolist() == [1, 0, 1, 2]


def test_csv_ingest(tmp_path):
    f = tmp_path / "edges.csv"
    f.write_text("src,dst,w,city\n1,2,0.5,sf\n3,4,1.25,nyc\n5,6,-2.0,sf\n")
    cols = csv_ingest(str(f), ["int", "int", "float", "strhash"])
    assert cols is not None
    assert cols[0].tolist() == [1, 3, 5]
    assert cols[1].tolist() == [2, 4, 6]
    assert cols[2].tolist() == [0.5, 1.25, -2.0]
    assert cols[3][0] == cols[3][2] == fnv1a("sf")
    assert cols[3][1] == fnv1a("nyc")


def test_row_codec_roundtrip():
    from nebula_tpu.native.kernels import decode_row, encode_row
    props = [("int", 42), ("double", 2.5), ("bool", True),
             ("str", "héllo; world"), ("null", None)]
    blob = encode_row(7, props)
    assert blob is not None and isinstance(blob, bytes)
    ver, got = decode_row(blob)
    assert ver == 7
    assert got == props
    # malformed input → clean None, not a crash
    assert decode_row(b"\x01") is None
    assert decode_row(blob[:-3]) is None


def test_csv_ingest_rejects_malformed(tmp_path):
    short = tmp_path / "short.csv"
    short.write_text("a,b\n1,2\n3\n")          # short row
    with pytest.raises(ValueError):
        csv_ingest(str(short), ["int", "int"])
    bad = tmp_path / "bad.csv"
    bad.write_text("a,b\n1,xyz\n")             # non-numeric int field
    with pytest.raises(ValueError):
        csv_ingest(str(bad), ["int", "int"])


def test_csv_ingest_rejects_truncation(tmp_path):
    f = tmp_path / "big.csv"
    f.write_text("a\n" + "\n".join(str(i) for i in range(100)) + "\n")
    with pytest.raises(ValueError):
        csv_ingest(str(f), ["int"], max_rows=10)


def test_build_csr_rejects_out_of_range():
    lib = get_lib()
    # a dense id whose local index exceeds vmax must fail cleanly
    src = np.asarray([0, 8 * 100], np.int64)   # local 100 >= vmax 5
    dst = np.zeros(2, np.int64)
    rank = np.zeros(2, np.int64)
    indptr = np.zeros((8, 6), np.int32)
    nbr = np.full((8, 2), -1, np.int32)
    rk = np.zeros((8, 2), np.int32)
    perm = np.full((8, 2), -1, np.int64)
    import ctypes as C

    def p(a):
        return a.ctypes.data_as(C.c_void_p)
    got = lib.build_csr(2, 8, 5, p(src), p(dst), p(rank), p(dst), p(perm),
                        p(indptr), p(nbr), p(rk), 2)
    assert got == -1


def test_snapshot_uses_native_and_matches_host_order():
    """End-to-end: CSR built through the native kernel must match
    get_neighbors row order exactly (the parity contract)."""
    from nebula_tpu.graphstore.csr import build_snapshot
    from nebula_tpu.graphstore.schema import PropDef, PropType
    from nebula_tpu.graphstore.store import GraphStore
    rng = random.Random(3)
    st = GraphStore()
    st.create_space("n", partition_num=4, vid_type="INT64")
    st.catalog.create_edge("n", "e", [PropDef("w", PropType.INT64)])
    st.catalog.create_tag("n", "t", [])
    for i in range(40):
        st.insert_vertex("n", i, "t", {})
    for _ in range(200):
        st.insert_edge("n", rng.randrange(40), "e", rng.randrange(40),
                       rng.randrange(3), {"w": rng.randrange(100)})
    snap = build_snapshot(st, "n")
    blk = snap.block("e", "out")
    sd = st.space("n")
    for vid in range(40):
        d = sd.dense_id(vid)
        if d < 0:
            continue
        p, li = d % 4, d // 4
        lo, hi = int(blk.indptr[p, li]), int(blk.indptr[p, li + 1])
        got = [(int(blk.rank[p, i]),
                sd.dense_to_vid[int(blk.nbr[p, i])],
                int(blk.props["w"][p, i]))
               for i in range(lo, hi)]
        want = [(rank, dst, props["w"])
                for (_, _, rank, dst, props, _) in st.get_neighbors(
                    "n", [vid], ["e"], "out")]
        assert got == want, vid


@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_join_halves_is_the_numpy_join_to_the_bit(dtype, monkeypatch):
    """A piece of a column's 32-bit halves joined by the library and by
    numpy's strided stores (the fallback): the same 64-bit column, for a
    contiguous piece, a slice of a wider one and an empty one."""
    from nebula_tpu.native import kernels
    from nebula_tpu.tpu.device import split_halves
    rng = np.random.default_rng(35)
    col = rng.integers(-2**63, 2**63 - 1, 10_001).view(dtype)
    wide = np.zeros((2, col.size + 9), np.uint32)
    wide[:, 4:4 + col.size] = split_halves(col)
    for pair in (split_halves(col), wide[:, 4:4 + col.size],
                 split_halves(col[:0])):
        want = col[:pair.shape[-1]].view(np.int64)
        got = np.full(pair.shape[-1], -1, dtype)
        kernels.join_halves(pair, got)
        assert (got.view(np.int64) == want).all()
        with monkeypatch.context() as m:
            m.setattr(kernels, "get_lib", lambda: None)
            got = np.full(pair.shape[-1], -1, dtype)
            kernels.join_halves(pair, got)
        assert (got.view(np.int64) == want).all()


_QNAN, _SNAN, _NEG_NAN = 0x7FF8000000000000, 0x7FF0000000000001, 0xFFF8000000000000
_LOW_HALF_NAN = 0x7FF00000FFFFFFFF       # the mantissa's set bits all in the low half
_HIGH_HALF_NAN = 0x7FF0000100000000      # ... all in the high half
_INF, _NEG_INF = 0x7FF0000000000000, 0xFFF0000000000000
_INT_NULL = 0x8000000000000000
# word -> is it the kind's NULL sentinel
SENTINELS = {
    np.float64: [(_QNAN, True), (_SNAN, True), (_NEG_NAN, True),
                 (_LOW_HALF_NAN, True), (_HIGH_HALF_NAN, True),
                 (0xFFFFFFFFFFFFFFFF, True), (_INF, False), (_NEG_INF, False),
                 (0x7FEFFFFFFFFFFFFF, False),        # the largest finite double
                 (_INT_NULL, False)],                # -0.0
    np.int64: [(_INT_NULL, True), (_INT_NULL + 1, False),
               (0x8000000100000000, False),          # the high half alone is not it
               (0x0000000080000000, False),          # nor that word as a LOW half
               (0, False), (0xFFFFFFFFFFFFFFFF, False), (_QNAN, False)],
}


@pytest.mark.parametrize("lib", ["native", "numpy"])
@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 8, 1001])
@pytest.mark.parametrize("at", ["none", "first", "last", "all"])
@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_join_halves_answers_the_null_question(dtype, at, n, lib, monkeypatch):
    """The joining pass's answer is the decode's scan of the joined
    column, `np.isnan(col).any()` / `(col == INT_NULL).any()`: with no
    NULL, one at the first slot, at the last (in the vector loop's tail
    and out of it), in every slot; for every NaN bit pattern and for no
    infinity; by the library and by the numpy fallback.  The joined
    words are the column's, whatever they hold."""
    from nebula_tpu.graphstore.csr import INT_NULL
    from nebula_tpu.native import kernels
    from nebula_tpu.tpu.device import split_halves
    if lib == "numpy":
        monkeypatch.setattr(kernels, "get_lib", lambda: None)
    elif not available():
        pytest.skip("no native library")
    rng = np.random.default_rng(n)
    for word, is_null in SENTINELS[dtype]:
        # finite doubles / small ints: no sentinel among them
        col = rng.integers(-2**40, 2**40, n).astype(dtype)
        where = {"none": [], "first": [0], "last": [n - 1],
                 "all": range(n)}[at]
        col.view(np.uint64)[list(where)] = word
        with np.errstate(invalid="ignore"):
            scan = bool(np.isnan(col).any() if dtype == np.float64
                        else (col == INT_NULL).any())
        assert scan == (is_null and at != "none")
        got = np.full(n, -1, dtype)
        assert kernels.join_halves(split_halves(col), got) is scan, hex(word)
        assert (got.view(np.uint64) == col.view(np.uint64)).all()


def test_join_halves_refuses_a_column_of_another_shape():
    from nebula_tpu.native import kernels
    pair = np.zeros((2, 5), np.uint32)
    for out in (np.zeros(4, np.int64), np.zeros(5, np.int32),
                np.zeros((5, 1), np.int64)):
        with pytest.raises(ValueError):
            kernels.join_halves(pair, out)
