"""`kernel.wide_operand_mb` (PR 35): the reader on a hand-built `ctx`,
and over a traced rehearsal of each proxy cell, where it has to read 0:
since edge property columns are pinned as their 32-bit halves no traverse
program takes a 64-bit operand (series `tpu_wide_operand_bytes`,
tpu/runtime.py `_escalate_locked`).

In `BENCHMARK.json` since PR 36 (MB, lower, `program_counter`, layer
"kernels", moves `stmts_per_s`, cells `snb-sf100-proxy.go3` and
`snb-sf300-proxy.go3-4chip`): PR 35 brought the reader and the series and
could not append the entry, because `test_write_read.py` then held the
manifest's last six per-layer entries."""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import loader  # noqa: E402

from test_phase_metrics import jax_config_restored  # noqa: E402,F401

NAME = "kernel.wide_operand_mb"
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_manifest_entry():
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)     # wherever it stands
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == \
        ("MB", "lower", "program_counter", "kernels", "stmts_per_s")
    assert {"snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip"} <= set(m["workloads"])
    assert set(m["workloads"]) <= {w["name"] for w in MANIFEST["workloads"]}


def _read(moved):
    return loader.module("layers", NAME).read({"counter": lambda name: moved.get(name, 0)})


def test_mb_a_program_run_over_the_windows_run():
    # the parent on four chips, had it kept the series: two columns of
    # 50,331,648 x 8 bytes a part, four parts, 190 program runs
    per_run = 2 * 4 * 50_331_648 * 8
    assert _read({"tpu_wide_operand_bytes.sum": 190 * per_run,
                  "tpu_wide_operand_bytes.count": 190}) == pytest.approx(3221.225472)
    # this tree: the series moves, by nothing
    assert _read({"tpu_wide_operand_bytes.count": 190}) == 0.0
    # a program without the series (the parent), or a window with no launch
    assert _read({}) is None
    assert loader.module("layers", NAME).NEEDS == ("tpu_wide_operand_bytes.count",)


@pytest.mark.parametrize("cell", ["snb-sf100-proxy.go3", "snb-sf300-proxy.go3-4chip"])
def test_reads_zero_in_the_proxy_cells_rehearsal(cell, capsys, jax_config_restored):  # noqa: F811
    from nebula_tpu.utils.stats import stats
    c0 = stats().snapshot()
    rc = bench_run.main(["--seconds", "1", "--rehearse", "--workload", cell,
                         "--seed", "2147483683", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    c1 = stats().snapshot()
    assert rc == 0 and line["rehearsal"]["checks_passed"] is True
    assert line["checks"]["rows_mismatched"]["value"] == 0
    # the double comes back to the bit, on every backend
    assert line["checks"]["float_rel_gap"]["value"] == 0
    moved = {k: v - c0.get(k, 0) for k, v in c1.items() if isinstance(v, (int, float))}
    assert moved["tpu_wide_operand_bytes.count"] == moved["tpu_kernel_runs"] > 0
    assert _read(moved) == 0.0
    # and the run's own line carries it, now that the manifest names it for the cell
    assert line["metrics"][NAME] == {"value": 0.0, "unit": "MB"}
