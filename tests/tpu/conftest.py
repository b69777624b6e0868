"""What the tests of this directory share: one small 4-part graph,
`E(w int, f double)`, as a prebuilt snapshot pinned on a 4-device mesh and
on one device, behind just enough of a GraphStore for
`TpuRuntime.traverse`."""
from __future__ import annotations

import numpy as np
import pytest


@pytest.fixture(scope="session")
def pinned_pair():
    """-> make(space, seed), a generator for a module's own fixture
    (`yield from`): (mesh runtime, local runtime, store), both unpinned
    when the module is done."""
    def make(space, seed):
        from nebula_tpu.graphstore.csr import CsrBlock, CsrSnapshot, StringPool
        from nebula_tpu.graphstore.schema import PropType
        from nebula_tpu.tpu.runtime import TpuRuntime

        rng = np.random.default_rng(seed)
        P, n, deg = 4, 800, 6
        vmax, emax = n // P, 2048
        src = np.repeat(np.arange(n), deg)
        dst = rng.integers(0, n, src.size)
        indptr = np.zeros((P, vmax + 1), np.int32)
        nbr = np.full((P, emax), -1, np.int32)
        w = np.full((P, emax), -2, np.int64)
        f = np.full((P, emax), np.nan, np.float64)
        for p in range(P):
            rows = np.flatnonzero(src % P == p)
            rows = rows[np.lexsort((dst[rows], src[rows] // P))]
            np.cumsum(np.bincount(src[rows] // P, minlength=vmax), out=indptr[p, 1:])
            nbr[p, :rows.size] = dst[rows]
            w[p, :rows.size] = dst[rows] % 100
            f[p, :rows.size] = dst[rows] / 7.0
        snap = CsrSnapshot(space=space, epoch=0, num_parts=P, vmax=vmax,
                           num_vertices=np.full(P, vmax, np.int32), pool=StringPool(),
                           dense_to_vid=list(range(n)))
        for d in ("out", "in"):
            snap.blocks[("E", d)] = CsrBlock(
                etype="E", direction=d, indptr=indptr, nbr=nbr, rank=np.zeros_like(nbr),
                props={"w": w, "f": f},
                prop_types={"w": PropType.INT64, "f": PropType.DOUBLE})

        class Space:
            epoch = 0

            @staticmethod
            def dense_id(v):
                return int(v) if 0 <= int(v) < n else -1

        class Edge:
            edge_type = 1

        class Catalog:
            def get_edge(self, space, etype):
                return Edge()

        class Store:
            """Just enough of a GraphStore for `TpuRuntime.traverse`."""
            catalog = Catalog()

            def space(self, name):
                return Space()

        mesh, local = TpuRuntime(n_devices=P), TpuRuntime(n_devices=1)
        mesh.pin_prebuilt(snap)
        local.pin_prebuilt(snap)
        yield mesh, local, Store()
        mesh.unpin(space)
        local.unpin(space)
    return make
