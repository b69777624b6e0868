"""Reference operation `go`: GO <steps> STEPS FROM <start> OVER <over>
[WHERE <etype>.w > w_gt] YIELD <cols>."""
from benchmarks.reference.graph import same_rows


def count(ref, t, start):
    return ref.go([start], t["steps"], t["over"], t.get("w_gt"), t["cols"],
                  count_only=True)[1]


def answer(ref, t, start):
    return ref.go([start], t["steps"], t["over"], t.get("w_gt"), t["cols"])[0]


def compare(reply, want):
    """-> (rows that differ, widest float gap or None, detail)"""
    return same_rows({c: reply.column(c) for c in want}, want)
