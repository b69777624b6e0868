"""Reference operation `trail_count`: MATCH (a)-[e:E*1..max_hop]->(b) WHERE
id(a) IN [start] RETURN count(*) — one row holding the number of trails."""


def answer(ref, t, start):
    return ref.trail_count([start], t["etype"], t["max_hop"])


def count(ref, t, start):
    return 1


def compare(reply, want):
    rows = reply.rows()
    got = int(rows[0][0]) if len(rows) == 1 else None
    return 0 if got == want else 1, None, f"{got} trails, reference {want}"
