"""Metric arithmetic kept with the benchmark: percentiles, quartile
spread, the hops' byte model and the table of peaks."""
from __future__ import annotations

import json
import math
import os
import statistics

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: bytes read per expanded edge: the neighbour id (int32 in the pinned CSR),
#: counted once per edge, and each yielded or filtered property at the width
#: of its type in the configuration's schema (a string is its pool code)
NBR_BYTES = 4
INDPTR_BYTES = 4
TYPE_BYTES = {"int": 8, "double": 8, "string": 8}


def percentile(samples, p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least p% of
    the samples at or below it."""
    s = sorted(samples)
    if not s:
        raise ValueError("percentile of no samples")
    return s[max(math.ceil(p / 100.0 * len(s)), 1) - 1]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's spread (statistics.quantiles, n=4)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def hop_bytes(hop_edges, frontier_sizes, props, schema) -> int:
    """Bytes an N-step GO has to read, from shapes only: per hop the
    frontier's two `indptr` entries per vertex and the neighbour id of
    every edge expanded; on the last hop also each property in `props`
    (yielded or filtered, by name) of every edge, at the width of its type
    in `schema` ({property: type}, the configuration's).  No writes, no
    FLOPs worth counting: the bound that applies to a hop is bytes."""
    total = 0
    last = len(hop_edges) - 1
    per_edge_last = NBR_BYTES + sum(TYPE_BYTES[schema[p]] for p in set(props))
    for h, edges in enumerate(hop_edges):
        total += int(frontier_sizes[h]) * 2 * INDPTR_BYTES
        total += int(edges) * (per_edge_last if h == last else NBR_BYTES)
    return total


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in benchmarks/peaks.json; "
                       f"add it with its source, there is no default")
    return table[device_kind]
