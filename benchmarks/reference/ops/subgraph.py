"""Reference operation `subgraph`: GET SUBGRAPH steps STEPS FROM start OUT
E YIELD VERTICES AS v, EDGES AS e — one row per step: the vertices first
reached at that step and the out-edges of them that stay inside the
subgraph, compared as sorted vids and sorted (src, dst) pairs."""


def answer(ref, t, start):
    return ref.subgraph(start, t["etype"], t["steps"])


def count(ref, t, start):
    return len(answer(ref, t, start))


def compare(reply, want):
    got = [(sorted(int(v.vid) for v in row[0]),
            sorted((int(e.src), int(e.dst)) for e in row[1])) for row in reply.rows()]
    bad = sum(g != w for g, w in zip(got, want)) + abs(len(got) - len(want))
    return bad, None, f"{len(got)} rows, reference {len(want)}"
