"""Builder `local_cluster`: the served path.  An in-process LocalCluster
(1 metad, 1 storaged with raft and WAL, 1 graphd holding TpuRuntime()),
the configuration's space and schema, the generator's rows written
through GraphClient INSERT statements — chip_smoke.py phase a's set-up,
the benchmark's copy.  Sets no program flag.  A session is one
GraphClient; a request is its statement text over the socket."""
from __future__ import annotations

import os
import shutil
import tempfile
import time

from benchmarks.lib.reply import Reply


def _literals(column, ty):
    """A generator's column as nGQL literals of the schema's type."""
    values = column.tolist() if hasattr(column, "tolist") else column
    if ty == "string":
        return [f'"{v}"' for v in values]
    return [repr(v) for v in values]


class Session:
    def __init__(self, client):
        self.client = client
        r = client.execute("USE snb")
        if r.error is not None:
            raise RuntimeError(f"USE snb -> {r.error}")

    def execute(self, request) -> Reply:
        rs = self.client.execute(request["text"])
        if rs.error is not None:
            return Reply(error=str(rs.error))
        return Reply(n_rows=len(rs.data), data=rs.data)

    def close(self):
        self.client.close()


class Deployment:
    served = True          # statements pass graphd: its counters move

    def __init__(self, cluster, tmp, stages):
        self.cluster, self.tmp, self.stages = cluster, tmp, stages

    def open_session(self) -> Session:
        return Session(self.cluster.client())

    def close(self):
        self.cluster.stop()
        shutil.rmtree(self.tmp, ignore_errors=True)


def build(cfg: dict, sizes: dict, tables: dict, say) -> Deployment:
    from nebula_tpu.cluster.launcher import LocalCluster
    from nebula_tpu.tpu.runtime import TpuRuntime

    sp = cfg["fixes"]["space"]
    tmp = tempfile.mkdtemp(prefix="bench_cluster_")
    t0 = time.perf_counter()
    cluster = LocalCluster(n_meta=1, n_storage=1, n_graph=1,
                           data_dir=os.path.join(tmp, "cluster"),
                           tpu_runtime=TpuRuntime())
    try:
        cl = cluster.client()

        def ex(q):
            r = cl.execute(q)
            if r.error is not None:
                raise RuntimeError(f"{q[:120]} -> {r.error}")

        ex(f"CREATE SPACE {sp['name']}(partition_num={sp['partition_num']}, "
           f"replica_factor={sp['replica_factor']}, vid_type={sp['vid_type']})")
        cluster.reconcile_storage()
        ex(f"USE {sp['name']}")
        schema = cfg["fixes"]["schema"]
        for kind in ("tags", "edges"):
            for name, props in schema[kind].items():
                ex(f"CREATE {kind[:-1].upper()} {name}("
                   + ", ".join(f"{p} {ty}" for p, ty in props.items()) + ")")
        cluster_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        B = int(sizes["insert_batch"])

        def insert(head, keys, props, columns):
            """Rows `key:(values)` in statements of B rows each."""
            vals = [_literals(columns[p], ty) for p, ty in props.items()]
            rows = [f"{k}:({', '.join(v)})" for k, *v in zip(keys, *vals)]
            for lo in range(0, len(rows), B):
                ex(f"INSERT {head}({', '.join(props)}) VALUES " + ", ".join(rows[lo:lo + B]))
            return len(rows)

        rows = 0
        for tag, props in schema["tags"].items():
            rows += insert(f"VERTEX {tag}", range(tables["n"]), props, tables["vertex"])
        for et, props in schema["edges"].items():
            e = tables["edges"][et]
            keys = [f"{s}->{d}" for s, d in zip(e["src"].tolist(), e["dst"].tolist())]
            rows += insert(f"EDGE {et}", keys, props, e)
        load_s = time.perf_counter() - t0
        cl.close()
        say(f"cluster up in {cluster_s:.1f}s; {rows} rows through GraphClient INSERTs "
            f"in {load_s:.1f}s ({rows / load_s:,.0f} rows/s)")
    except BaseException:
        cluster.stop()
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return Deployment(cluster, tmp, {"cluster_s": cluster_s, "load_s": load_s})
