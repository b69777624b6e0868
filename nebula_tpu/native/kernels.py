"""High-level wrappers over the native library, with NumPy fallbacks.

build_coo_csr: COO edge arrays → padded per-part CSR + permutation (the
snapshot builder's hot loop).  csv_ingest: delimited file → typed
columns.  row codec: binary row encode/decode (bulk export format).
"""
from __future__ import annotations

import ctypes
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import get_lib


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def build_coo_csr(src_dense: np.ndarray, dst_dense: np.ndarray,
                  rank: np.ndarray, dst_key: np.ndarray, P: int,
                  vmax: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray, int]:
    """→ (indptr (P, vmax+1) i32, nbr (P, emax) i32, rank (P, emax) i32,
    perm (P, emax) i64, emax).  perm[p, slot] is the COO index whose
    edge landed in that slot (for property-column gathers); -1 pad."""
    n = int(src_dense.shape[0])
    if n == 0:
        return (np.zeros((P, vmax + 1), np.int32),
                np.full((P, 1), -1, np.int32),
                np.zeros((P, 1), np.int32),
                np.full((P, 1), -1, np.int64), 1)
    src_dense = np.ascontiguousarray(src_dense, np.int64)
    dst_dense = np.ascontiguousarray(dst_dense, np.int64)
    rank = np.ascontiguousarray(rank, np.int64)
    dst_key = np.ascontiguousarray(dst_key, np.int64)
    counts = np.bincount((src_dense % P).astype(np.int64), minlength=P)
    emax = max(1, int(counts.max()))

    lib = get_lib()
    if lib is not None:
        indptr = np.zeros((P, vmax + 1), np.int32)
        nbr = np.full((P, emax), -1, np.int32)
        rk = np.zeros((P, emax), np.int32)
        perm = np.full((P, emax), -1, np.int64)
        got = lib.build_csr(n, P, vmax, _ptr(src_dense), _ptr(dst_dense),
                            _ptr(rank), _ptr(dst_key), _ptr(perm),
                            _ptr(indptr), _ptr(nbr), _ptr(rk), emax)
        if got == emax:
            return indptr, nbr, rk, perm, emax
        # fall through to numpy on unexpected failure
    return _numpy_coo_csr(src_dense, dst_dense, rank, dst_key, P, vmax,
                          emax)


def _numpy_coo_csr(src_dense, dst_dense, rank, dst_key, P, vmax, emax):
    """The pure-numpy twin of the native build (identical slot order:
    part, local, rank, dst_key, idx) — the fallback AND the property
    tests' oracle for the C path."""
    n = int(src_dense.shape[0])
    part = src_dense % P
    local = src_dense // P
    order = np.lexsort((np.arange(n), dst_key, rank, local, part))
    indptr = np.zeros((P, vmax + 1), np.int32)
    nbr = np.full((P, emax), -1, np.int32)
    rk = np.zeros((P, emax), np.int32)
    perm = np.full((P, emax), -1, np.int64)
    pos = np.zeros(P, np.int64)
    sp = part[order]
    sl = local[order]
    for k in range(n):
        p = int(sp[k])
        slot = int(pos[p])
        pos[p] += 1
        e = int(order[k])
        perm[p, slot] = e
        nbr[p, slot] = dst_dense[e]
        rk[p, slot] = rank[e]
        indptr[p, sl[k] + 1] += 1
    np.cumsum(indptr, axis=1, out=indptr)
    return indptr, nbr, rk, perm, emax


# graphstore/csr.py `INT_NULL` (csr.py imports this module, not the reverse)
_INT_NULL = np.iinfo(np.int64).min


def join_halves(pair: np.ndarray, out: np.ndarray) -> bool:
    """A piece `(2, n)` `uint32` of a property column's 32-bit halves,
    low half first (tpu/device.py `split_halves`), joined into the `n`
    64-bit slots of `out`.  One pass in the library, at a copy's rate
    and without the GIL; numpy's two strided stores a row, at half that
    rate, where the library is missing.

    The same pass answers what the decode asks of the column
    (graphstore/csr.py `decode_prop_column_np`): whether any joined slot
    holds the NULL sentinel of `out`'s kind, a NaN of any bit pattern
    for a float column, `INT_NULL` for an integer one."""
    n = pair.shape[-1]
    if out.shape != (n,) or out.dtype.itemsize != 8:
        raise ValueError(f"join_halves: {pair.shape} halves into "
                         f"{out.dtype}{out.shape}")
    is_float = out.dtype.kind == "f"
    lib = get_lib()
    if (lib is not None and pair.dtype == np.uint32
            and pair.strides[-1] == 4 and out.flags.c_contiguous):
        return bool(lib.join_halves(pair[0].ctypes.data, pair[1].ctypes.data,
                                    out.ctypes.data, n, int(is_float)))
    words = out.view(np.uint32).reshape(n, 2)
    words[:, 0], words[:, 1] = pair[0], pair[1]
    return bool(np.isnan(out).any() if is_float
                else (out.view(np.int64) == _INT_NULL).any())


def dst_sort_key(dst_vids: Sequence) -> np.ndarray:
    """int64 ordering key per neighbor: the vid itself for ints, the
    sorted-unique ordinal for strings (matches _nbr_key)."""
    if not dst_vids:
        return np.zeros(0, np.int64)
    if isinstance(dst_vids[0], int):
        return np.asarray(dst_vids, np.int64)
    arr = np.asarray([str(v) for v in dst_vids], dtype=object)
    _, inv = np.unique(arr, return_inverse=True)
    return inv.astype(np.int64)


def csv_ingest(path: str, col_types: List[str], delim: str = ",",
               skip_header: bool = True, max_rows: Optional[int] = None
               ) -> Optional[List[np.ndarray]]:
    """Parse a delimited file natively. col_types: 'int' | 'float' |
    'strhash' | 'skip'.  Returns per-column arrays (int64 for
    int/strhash, float64 for float, None for skip); None if the native
    library is unavailable (caller uses csv.reader).  Raises ValueError
    if the file exceeds max_rows (never truncates silently)."""
    import os
    lib = get_lib()
    if lib is None:
        return None
    tmap = {"int": 0, "float": 1, "strhash": 2, "skip": 3}
    kinds = [tmap[t] for t in col_types]
    n_cols = len(kinds)
    if max_rows is None:
        # a row needs >= n_cols delimiters/newline bytes, so the row
        # count is bounded by size/n_cols — sizes buffers to the file
        # instead of a fixed half-GB-per-column worst case
        max_rows = os.path.getsize(path) // max(1, n_cols) + 2
    ctypes_kinds = (ctypes.c_int * n_cols)(*kinds)
    icols = [np.zeros(max_rows, np.int64) if k in (0, 2)
             else np.zeros(0, np.int64) for k in kinds]
    dcols = [np.zeros(max_rows, np.float64) if k == 1
             else np.zeros(0, np.float64) for k in kinds]
    iptrs = (ctypes.c_void_p * n_cols)(*[_ptr(a) for a in icols])
    dptrs = (ctypes.c_void_p * n_cols)(*[_ptr(a) for a in dcols])
    n = lib.csv_ingest(path.encode(), delim.encode(), int(skip_header),
                       n_cols, ctypes_kinds, max_rows, iptrs, dptrs)
    if n == -2:
        raise ValueError(f"{path}: more rows than max_rows={max_rows}")
    if n == -3:
        raise ValueError(f"{path}: malformed record (short row or "
                         f"unparseable int/float field)")
    if n < 0:
        return None
    out: List[Optional[np.ndarray]] = []
    for i, k in enumerate(kinds):
        if k in (0, 2):
            out.append(icols[i][:n].copy())
        elif k == 1:
            out.append(dcols[i][:n].copy())
        else:
            out.append(None)
    return out


def encode_row(version: int, props: List[tuple]) -> Optional[bytes]:
    """Binary row encode (RowWriterV2 analog).  props: list of
    (kind, value) with kind in {'null','int','double','bool','str'}.
    None if the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    kmap = {"null": 0, "int": 1, "double": 2, "bool": 3, "str": 4}
    n = len(props)
    kinds = (ctypes.c_int * n)(*[kmap[k] for k, _ in props])
    ivals = (ctypes.c_int64 * n)()
    dvals = (ctypes.c_double * n)()
    svals = (ctypes.c_char_p * n)()
    slens = (ctypes.c_int * n)()
    bufs = []                       # keep encoded strings alive
    need = 4
    for i, (k, v) in enumerate(props):
        need += 1
        if k == "int":
            ivals[i] = int(v)
            need += 8
        elif k == "double":
            dvals[i] = float(v)
            need += 8
        elif k == "bool":
            ivals[i] = int(bool(v))
            need += 1
        elif k == "str":
            b = v.encode() if isinstance(v, str) else bytes(v)
            bufs.append(b)
            svals[i] = b
            slens[i] = len(b)
            need += 4 + len(b)
    out = (ctypes.c_ubyte * need)()
    got = lib.row_encode(version, n, kinds, ivals, dvals, svals, slens,
                         out, need)
    if got < 0:
        return None
    return bytes(out[:got])


def decode_row(data: bytes, max_props: int = 256
               ) -> Optional[tuple]:
    """→ (version, [(kind, value), ...]) or None (lib unavailable or
    malformed input)."""
    lib = get_lib()
    if lib is None:
        return None
    buf = (ctypes.c_ubyte * len(data)).from_buffer_copy(data)
    ver = ctypes.c_int()
    kinds = (ctypes.c_int * max_props)()
    ivals = (ctypes.c_int64 * max_props)()
    dvals = (ctypes.c_double * max_props)()
    soffs = (ctypes.c_longlong * max_props)()
    slens = (ctypes.c_int * max_props)()
    n = lib.row_decode(buf, len(data), ctypes.byref(ver), kinds, ivals,
                       dvals, soffs, slens, max_props)
    if n < 0:
        return None
    rmap = {0: "null", 1: "int", 2: "double", 3: "bool", 4: "str"}
    out = []
    for i in range(n):
        k = rmap[kinds[i]]
        if k == "int":
            out.append((k, int(ivals[i])))
        elif k == "double":
            out.append((k, float(dvals[i])))
        elif k == "bool":
            out.append((k, bool(ivals[i])))
        elif k == "str":
            out.append((k, data[soffs[i]:soffs[i] + slens[i]].decode()))
        else:
            out.append((k, None))
    return ver.value, out


def fnv1a(s: str) -> int:
    """Python mirror of the native string hash (for joining strhash
    columns back to actual strings)."""
    h = 1469598103934665603
    for b in s.encode():
        h ^= b
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h - (1 << 64) if h >= (1 << 63) else h
