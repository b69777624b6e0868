"""What a level-synchronous BFS over P owners has to exchange, from
shapes alone: after every level each owner hands every owner (itself
among them, as a collective counts it) one bit for each of that owner's
`vmax` vertices, packed into 32-bit words.  Independent of the program:
nothing here is read from its counters or its trace."""
WORD_BYTES = 4


def bfs_mesh_bytes(levels: int, parts: int, vmax: int) -> int:
    """Bytes all `parts` owners send over `levels` levels."""
    return int(levels) * int(parts) * int(parts) * -(-int(vmax) // 32) * WORD_BYTES
