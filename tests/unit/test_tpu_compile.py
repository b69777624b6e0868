"""The main path's device programs, compiled for a DESCRIBED TPU v5e at
the real (north-star) shapes — no chip attached, nothing runs.

What interpret-mode and CPU-backend tests cannot show: a program the
TPU compiler refuses, one that does not fit a chip's 16 GB, a sharded
program that lost its collective, or a kernel whose compile takes
minutes (the float64 `jnp.cumsum` that `pagerank_step` used to carry
needed 194 s at 600,000 edges).  A compile that passes here is not a
chip run; `chip_smoke.py` is.

The topology is described inside a module-scoped fixture (only the
xdist worker that runs THIS file loads libtpu, and only after a test
has started), never at import, and all of these tests live in this one
file for the same reason.  The persistent compile cache is off around
them: an entry compiled for a described device cannot be read back.
"""
import os
import re
import time

import numpy as np
import pytest

# north-star shapes: 1,000,000 persons x degree 30 over 8 parts
P8, VMAX8, E8 = 8, 125_000, 4_194_304
# the same graph sharded one partition per chip of a 2x2 host
P4, VMAX4, E4 = 4, 250_000, 8_388_608
N_SLOTS, N_EDGES = 1_000_000, 30_000_000
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    import nebula_tpu.tpu  # noqa: F401 — enables x64 like every caller
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _block(P, vmax, E, sharding, props=("w",), rev=False, dcap=0):
    """One CSR block's kernel leaves as shapes (runtime.py
    `_block_leaves`); with `dcap`, an armed delta plane's too
    (`_grab_delta`).  A property column is pinned as its 32-bit bit
    halves (device.py `split_halves`)."""
    def cols(width):
        return {n: _struct((P, 2, width), np.uint32, sharding)
                for n in props}
    b = {"indptr": _struct((P, vmax + 1), np.int32, sharding),
         "nbr": _struct((P, E), np.int32, sharding),
         "rank": _struct((P, E), np.int32, sharding),
         "props": cols(E)}
    if dcap:
        b.update({k: _struct((P, dcap), np.bool_ if k == "d_valid"
                             else np.int32, sharding)
                  for k in ("d_src", "d_dst", "d_rank", "d_valid", "d_tomb")},
                 d_props=cols(dcap))
    if rev:
        b.update(rev_indptr=b["indptr"], rev_nbr=b["nbr"],
                 rev_rank=b["rank"], rev_props={})
    return b


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert need < HBM_BYTES, f"program needs {need:,} bytes of HBM"
    return compiled, time.perf_counter() - t0


@pytest.mark.parametrize("meshed,lanes,carry_rank", [
    (False, False, True), (True, False, True), (False, True, True),
    (True, True, True), (False, True, False), (True, True, False)],
    ids=["one-chip", "mesh", "one-chip-lanes", "mesh-lanes",
         "one-chip-lanes-rank-free", "mesh-lanes-rank-free"])
def test_go_three_steps_capture_compiles(topo, one_chip, meshed, lanes,
                                         carry_rank):
    """The north-star statement: 3-step GO, final hop captured with an
    int64 prop gathered on device (YIELD dst(edge), KNOWS.w), in each
    layout of the one builder entry: all eight parts on one chip, or one
    partition per chip of the 2x2 host, whose frontier exchange must
    still be an all-to-all in the compiled program (ONE a hop also
    under four query lanes); solo, or four lanes to a launch; the lane
    layouts also as a statement that reads no rank gets them (PR 38:
    no `rank` gather, carry or capture; the solo layouts' rank-free
    programs are the two cells' below)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from nebula_tpu.tpu.hop import build_traverse_fn
    L = (4,) if lanes else ()
    if meshed:
        mesh = Mesh(np.asarray(topo.devices[:P4]), ("part",))
        part = NamedSharding(mesh, PartitionSpec("part"))
        fr = NamedSharding(mesh, PartitionSpec(None, "part") if lanes
                           else PartitionSpec("part"))
        P, vmax, E, ebs = P4, VMAX4, E4, (1 << 12, 1 << 17, 1 << 21)
    else:
        mesh, part, fr = None, one_chip, one_chip
        P, vmax, E, ebs = P8, VMAX8, E8, (1 << 12, 1 << 17, 1 << 22)
    fn = build_traverse_fn(mesh, P, ebs, 3, n_blocks=1, lanes=lanes,
                           capture=True, yield_cols=("w",),
                           carry_rank=carry_rank)
    compiled, _ = _compile(fn, (_block(P, vmax, E, part),),
                           _struct(L + (P, vmax), np.bool_, fr))
    assert (compiled.as_text().count(" all-to-all(") == 2) == meshed
    # a slot of the last hop gathers `nbr` and the row offsets (bitmaps
    # this wide take the member plan), and the rank where it is carried
    assert fn.noted["slot_gathers"] == 2 + carry_rank


def _w_over_50(cols):
    from nebula_tpu.tpu.device import join_halves
    return join_halves(cols["w"], np.int64) > 50


def _f_over_half(cols):
    from nebula_tpu.tpu.device import join_halves, nan_halves
    return (join_halves(cols["f"], np.float64) > 0.5) & ~nan_halves(cols["f"])


def _no_column_is_split(text):
    """No run of this program splits a 64-bit operand into its halves
    (`X64SplitLow` / `X64SplitHigh` over a whole `E`-slot column, two
    passes a column, was 28% of the four-chip cell's device time): the
    columns arrive as the halves, a predicate's 64-bit value is rebuilt
    per gathered slot (device.py `join_halves`).  Nor does it ask the
    compiler to reinterpret 64 bits as a double, which a TPU cannot do
    exactly (it answers NaN for a finite double beyond float32's range):
    a double is built from 32-bit pieces."""
    assert "X64Split" not in text
    assert not re.search(r"= [fsu]64\[[^=]*bitcast-convert\(", text)


@pytest.mark.parametrize("filtered", [False, "w", "f", "rank-free"],
                         ids=["carried", "filtered", "filtered_f",
                              "carried-rank-free"])
def test_proxy_cell_go3_by_need_loops_compile(one_chip, filtered):
    """`snb-sf100-proxy.go3`'s program (benchmarks/configs): budgets
    (2048, 2^20, 2^22) a part, YIELD dst, w, f — the last two hops run
    their gathers in by-need loops (hop.py `_by_need`; the int64 `w` and
    the float64 `f` are 32-bit pairs as operands, through the loop
    carry and in the capture).  A loop the TPU compiler rejects, or
    takes minutes over, fails here and not first on the chip.  The
    cell's own program since PR 38 is `carried-rank-free`: its
    statements read no rank and `pin_prebuilt` arms no plane, so its
    last hop gathers `nbr` and the row offsets alone and no buffer of
    8 x 2^22 ranks exists."""
    from nebula_tpu.tpu.hop import CHUNK, build_traverse_fn
    ebs = (1 << 11, 1 << 20, 1 << 22)
    assert ebs[0] <= CHUNK < ebs[1], "the cell no longer exercises the loop"
    kw = {"w": dict(pred=_w_over_50, pred_cols=("w",)),
          "f": dict(pred=_f_over_half, pred_cols=("f",)),
          "rank-free": dict(carry_rank=False)}.get(filtered, {})
    fn = build_traverse_fn(None, P8, ebs, 3, n_blocks=1, capture=True,
                           yield_cols=("f", "w"), **kw)
    compiled, secs = _compile(
        fn, (_block(P8, VMAX8, E8, one_chip, props=("f", "w")),),
        _struct((P8, VMAX8), np.bool_, one_chip))
    assert secs < 120, f"the proxy cell's program took {secs:.0f}s to compile"
    text = compiled.as_text()
    # the second hop's expansion, the last hop's, its property gathers
    assert text.count(" while(") >= 3
    _no_column_is_split(text)
    free = filtered == "rank-free"
    assert fn.noted["slot_gathers"] == {
        False: 3, "w": 4, "f": 4, "rank-free": 2}[filtered]
    # src, dst, eidx and two yielded columns' halves come back; rank
    # only from a program that carries it
    out = compiled.memory_analysis().output_size_in_bytes
    col = P8 * ebs[-1] * 4      # one identity column of the capture
    assert (7 if free else 8) * col < out < (7.5 if free else 8.5) * col


@pytest.mark.parametrize("filtered", [False, True], ids=["go1", "go3w"])
def test_served_go_with_an_armed_delta_plane_compiles(one_chip, filtered):
    """What the served cells run since the delta plane is armed at
    default flags (10,000 persons over 8 parts, the capacity the rule
    gives: 1,024): a capture EB + 1,024 wide whose merge stages each sit
    behind a `cond` on the plane's live count.  The TPU compiler has to
    take a `cond` inside a by-need loop's body and around one."""
    from nebula_tpu.tpu.hop import build_traverse_fn
    P, vmax, E, dcap = 8, 1250 + 64, 40_960, 1 << 10
    if filtered:
        fn = build_traverse_fn(None, P, (8192,) * 3, 3, n_blocks=1,
                               capture=True, pred=_w_over_50,
                               pred_cols=("w",), yield_cols=("w",))
        props = ("w",)
    else:
        fn = build_traverse_fn(None, P, (8192,), 1, n_blocks=1,
                               capture=True, yield_cols=("f", "w"))
        props = ("f", "w")
    compiled, secs = _compile(
        fn, (_block(P, vmax, E, one_chip, props=props, dcap=dcap),),
        _struct((P, vmax), np.bool_, one_chip))
    assert secs < 60, f"an armed program took {secs:.0f}s to compile"
    # the tombstone test, the rows, the compaction or the prefix, the
    # delta column's gathers: each a conditional in the compiled program
    assert compiled.as_text().count(" conditional(") >= 4


@pytest.mark.parametrize("shape", [(P8, 1, 1 << 22), (1, 1, 1 << 19)],
                         ids=["proxy", "mesh-shard"])
def test_fetch_pieces_compile(one_chip, shape):
    """The fetch programs of a wide capture (fetch.py `_piece`: of
    every column ONE dynamic_slice with the row and the first slot
    traced) at the proxy cells' shapes, every size of the ladder that
    fits: the one-chip cell's (8, 1, 2^22) columns and one shard of the
    four-chip cell's."""
    from nebula_tpu.tpu import fetch
    cap = {n: _struct(shape, np.int32, one_chip)
           for n in ("src", "dst", "rank", "eidx")}
    cap.update({n: _struct(shape[:-1] + (2, shape[-1]), dt, one_chip)
                for n, dt in [("prop:w", np.uint32), ("prop:f", np.uint32)]})
    at = _struct((len(shape),), np.int32, one_chip)
    for size in (c for c in fetch.PIECES if c <= shape[-1]):
        compiled, secs = _compile(fetch._piece, cap, at, size)
        assert secs < 20
        # a property column is captured as its halves: no piece splits
        # a 268 MB operand whole before its slice any more
        _no_column_is_split(compiled.as_text())


def test_match_var_len_capture_hops_compiles(one_chip):
    """MATCH *1..4: four hops, every hop's frame captured."""
    from nebula_tpu.tpu.hop import build_traverse_fn
    fn = build_traverse_fn(None, P8, 1 << 20, 4, n_blocks=1,
                           capture=True, capture_hops=True)
    _compile(fn, (_block(P8, VMAX8, E8, one_chip, props=()),),
             _struct((P8, VMAX8), np.bool_, one_chip))


@pytest.mark.parametrize("filtered", [False, True, "rank-free"],
                         ids=["carried", "filtered", "carried-rank-free"])
def test_mesh_cell_go3_compiles_for_the_four_chip_host(topo, filtered):
    """`snb-sf300-proxy.go3-4chip`'s program (benchmarks/configs): 6 M
    persons over 4 parts of 50,331,648 padded slots, budgets (2048, 2^16,
    2^21) a part, YIELD dst, w, f, one partition per chip of the 2x2
    host.  Each chip is handed a quarter of the blocks the statement
    reads (1.2 GB of the 3.2 GB it holds) and two frontier exchanges
    stay in the program: a traverse skips the last hop's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from nebula_tpu.tpu.hop import a2a_payload_bytes, build_traverse_fn
    vmax, width = 1_500_000, 50_331_648
    mesh = Mesh(np.asarray(topo.devices[:P4]), ("part",))
    part = NamedSharding(mesh, PartitionSpec("part"))
    kw = {True: dict(pred=_w_over_50, pred_cols=("w",)),
          "rank-free": dict(carry_rank=False)}.get(filtered, {})
    fn = build_traverse_fn(mesh, P4, (1 << 11, 1 << 16, 1 << 21), 3,
                           n_blocks=1, capture=True, yield_cols=("f", "w"),
                           **kw)
    compiled, secs = _compile(
        fn, (_block(P4, vmax, width, part, props=("f", "w")),),
        _struct((P4, vmax), np.bool_, part))
    assert secs < 120, f"the mesh cell's program took {secs:.0f}s to compile"
    text = compiled.as_text()
    assert text.count(" all-to-all(") == 2 and " all-reduce(" not in text
    _no_column_is_split(text)
    # each chip sends a row of ceil(vmax / 32) words to each of the four
    assert f"u32[{P4},1,{-(-vmax // 32)}]" in text
    assert a2a_payload_bytes(P4, vmax) == P4 * P4 * -(-vmax // 32) * 4
    args = compiled.memory_analysis().argument_size_in_bytes
    # the cell's own program since PR 38 is the rank-free one: a part's
    # `rank` column (4 bytes a slot) is not even an argument of it
    free = filtered == "rank-free"
    assert 1.1e9 - free * 4 * width < args < 1.3e9 - free * 4 * width, args
    assert fn.noted["slot_gathers"] == {
        False: 3, True: 4, "rank-free": 2}[filtered]


def test_bfs_compiles(one_chip):
    """FIND SHORTEST PATH's direction-optimizing single-chip BFS."""
    from nebula_tpu.tpu.bfs import build_bfs_fn_local
    fn = build_bfs_fn_local(P8, 1 << 22, 5, VMAX8, have_rev=True)
    _compile(fn, (_block(P8, VMAX8, E8, one_chip, props=(), rev=True),),
             _struct((P8, VMAX8), np.bool_, one_chip))


@pytest.mark.parametrize("have_rev", [True, False], ids=["either-way", "top-down"])
def test_sharded_bfs_compiles_at_the_mesh_cells_size(topo, have_rev):
    """FIND SHORTEST PATH over a graph one chip refuses (the cell
    `snb-sf300-paths-proxy.bfs5-4chip`, PR 43): one part of 1,500,000
    vertices and 50,331,648 padded slots a chip, the last level's budget
    the part's whole width, which is over the traverse ladder's 2^24.
    The level loops carry one flat bitmap, so what a level needs beside
    the pinned part is its plan (4 bytes a slot, twice), and every
    level ends in ONE all-to-all.

    With the reverse blocks (the cell's own program since PR 45) the
    four levels whose budgets loop choose their direction: one
    conditional each, which holds the two loops and no plan (a plan's
    running maximum over 50 M slots compiled for 54 s inside a branch:
    the whole program must stay near the top-down one's seconds), and
    before it the gather of the frontier bitmap and the reduction that
    settles the choice, a collective each."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from nebula_tpu.algo.frontier import LEVEL_CHUNK
    from nebula_tpu.tpu.bfs import (bfs_exchange_bytes, bfs_gather_bytes,
                                    build_bfs_fn)
    vmax, width = 1_500_000, 50_331_648
    assert width > 1 << 24 and width % LEVEL_CHUNK == 0
    mesh = Mesh(np.asarray(topo.devices[:P4]), ("part",))
    part = NamedSharding(mesh, PartitionSpec("part"))
    fn = build_bfs_fn(mesh, P4, (2048, 1 << 14, 1 << 18, 1 << 24, width),
                      5, vmax, have_rev=have_rev)
    compiled, secs = _compile(
        fn, (_block(P4, vmax, width, part, props=(), rev=have_rev),),
        _struct((P4, vmax), np.bool_, part))
    assert secs < 120, f"the sharded BFS took {secs:.0f}s to compile"
    text = compiled.as_text()
    choose = 4 * have_rev
    assert fn.gather_levels == choose
    assert text.count(" all-to-all(") == 5
    assert text.count(" conditional(") == choose
    # the compiler may turn the small all-gather into an all-reduce
    assert text.count(" all-gather(") + text.count(" all-reduce(") == 2 * choose
    assert f"u32[{P4},1,{-(-vmax // 32)}]" in text
    assert bfs_exchange_bytes(P4, vmax, 5) == 5 * P4 * P4 * -(-vmax // 32) * 4
    assert bfs_gather_bytes(P4, vmax, choose) == choose * P4 * P4 * -(-vmax // 32) * 4
    ma = compiled.memory_analysis()
    # a chip's share: the part's row offsets and neighbour ids are the
    # arguments, once a direction pinned (no predicate, so neither `rank`
    # nor a column; 4 bytes a slot and a vertex, the seed bitmap, some
    # tiling), and the temporaries stay within three budget-wide int32
    # arrays whichever way a level goes
    ways = 1 + have_rev
    assert ways * 4 * width < ma.argument_size_in_bytes < ways * 1.1 * 4 * (width + vmax)
    assert ma.temp_size_in_bytes < 3 * 4 * width, ma.temp_size_in_bytes


# the north star's shapes, and the deployment `graphalytics-dg75-proxy`'s
# (PR 47: 633,432 persons, 68.4 M directed rows; WCC reads them both ways)
ALGO_SHAPES = {"north-star": (N_SLOTS, N_EDGES), "graphalytics-dg75": (633_432, 68_400_000)}


@pytest.mark.parametrize("shape", sorted(ALGO_SHAPES))
@pytest.mark.parametrize("algo", ["pagerank", "wcc", "sssp"])
def test_algo_step_compiles_fast(one_chip, algo, shape):
    """The CALL algo.* iteration kernels at 1,000,000 slots /
    30,000,000 edges, and at the Graphalytics cell's own shapes.  The
    bound is the issue's: under two minutes (each takes about a second;
    the old float64 cumsum in pagerank_step did not finish in ten
    minutes)."""
    from nebula_tpu.algo import kernels
    n_slots, n_edges = ALGO_SHAPES[shape]
    if algo == "wcc" and shape != "north-star":
        n_edges *= 2

    def v(dt):
        return _struct((n_slots,), dt, one_chip)

    def e(dt):
        return _struct((n_edges,), dt, one_chip)
    if algo == "pagerank":
        fn = kernels.pagerank_step(n_slots, 0.85, 0.0)
        args = (v(np.float64), e(np.int32), e(np.int32), e(np.float64),
                v(np.bool_), v(np.bool_), float(n_slots))
    elif algo == "wcc":
        fn = kernels.wcc_step(n_slots)
        args = (v(np.int64), v(np.bool_), e(np.int32), e(np.int32))
    else:
        fn = kernels.sssp_step(n_slots, True)
        args = (v(np.float64), v(np.bool_), e(np.int32), e(np.int32),
                e(np.float64))
    _, secs = _compile(fn, *args)
    assert secs < 120, f"{algo}_step took {secs:.0f}s to compile"
