"""What a whole-graph algorithm has to move, from shapes and the plain
REFERENCE's counts alone (`reference/ops/<algo>.py` `profile`), a floor
whatever implements a step: an edge row is its two ids (int32 in the
pinned CSR) and what is gathered along it, a vertex is its state read and
written once (a double or a 64-bit label).

- PageRank: every one of its fixed iterations reads every row (two ids,
  one gathered double) and every vertex's rank, and writes it.
- WCC: ONE pass over the rows (two ids each) and the labels: no program
  can name the components having read less, however many passes it takes.
- SSSP: the rows out of the vertices the source reaches, once each (two
  ids, the weight, the gathered distance), and the distances."""
from benchmarks.lib.arith import NBR_BYTES

ALGOS = ("pagerank", "wcc", "sssp")
VALUE_BYTES = 8     # a rank, a distance, a weight, a label


def algo_bytes(ran: dict) -> int:
    """`ran`: a reference operation's `profile`."""
    rows, state = int(ran["rows"]), int(ran["vertices"]) * 2 * VALUE_BYTES
    if ran["algo"] == "pagerank":
        return int(ran["iterations"]) * (rows * (2 * NBR_BYTES + VALUE_BYTES) + state)
    if ran["algo"] == "wcc":
        return rows * 2 * NBR_BYTES + state
    if ran["algo"] == "sssp":
        return rows * (2 * NBR_BYTES + 2 * VALUE_BYTES) + state
    raise ValueError(f"no byte model for {ran['algo']!r}")
