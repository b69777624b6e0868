"""Reference operation `shortest_path`: FIND SHORTEST PATH FROM start TO
target OVER E UPTO max_steps STEPS — every shortest path, compared as the
sorted list of its vertex ids.  The target ($t) is the smallest vid the
reference's BFS finds `target_depth` levels out (or at the deepest level
there is)."""


def _target(ref, t, start):
    dist = ref.bfs_levels(start, t["etype"], t["max_steps"])
    depth = min(int(t["target_depth"]), int(dist.max()))
    return int((dist == depth).argmax())


def params(ref, t, start):
    return {"$t": str(_target(ref, t, start))}


def answer(ref, t, start):
    return ref.shortest_paths(start, _target(ref, t, start), t["etype"], t["max_steps"])


def count(ref, t, start):
    return len(answer(ref, t, start))


def compare(reply, want):
    got = sorted(tuple(int(v.vid) for v in row[0].nodes()) for row in reply.rows())
    bad = len(set(got) ^ set(want)) + abs(len(got) - len(want))
    return bad, None, f"{len(got)} paths, reference {len(want)}"
