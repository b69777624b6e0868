"""What a level-synchronous top-down BFS has to move, from the level
profile of the plain reference alone (reference/ops/bfs_levels.py
`profile`), so that the count is the same whatever implements a level:
per level expanded the frontier's two row offsets a vertex and the
neighbour id of every out-edge of the frontier, and one level written a
vertex.  A bottom-up level may stop at a vertex's first hit and read
fewer ids than this: the share built on it is of the top-down roofline."""
from benchmarks.lib.arith import INDPTR_BYTES, NBR_BYTES

LEVEL_BYTES = 4     # a vertex's level as the program returns it (int32)


def bfs_bytes(expanded, vertices: int) -> int:
    """`expanded`: [frontier size, out-edges of the frontier] per level."""
    return sum(int(f) * 2 * INDPTR_BYTES + int(e) * NBR_BYTES for f, e in expanded) \
        + int(vertices) * LEVEL_BYTES
