"""Reference operation `match_agg`: MATCH (p)-[:E]->(f)-[:E]->(ff) WHERE
id(p) IN [start] AND ff.age > min_age RETURN id(ff) AS v, count(*) AS c —
a two-hop trail join, grouped by its end."""
from benchmarks.reference.graph import same_rows


def answer(ref, t, start):
    return ref.match_agg([start], t["etype"], t["min_age"])


def count(ref, t, start):
    return int(answer(ref, t, start)["v"].size)


def compare(reply, want):
    return same_rows({c: reply.column(c) for c in want}, want)
