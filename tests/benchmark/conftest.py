"""One assertion of this directory that PR 39 ends, held by a file a
program PR may not edit.

`test_phase_metrics.py::test_a_traced_rehearsal_prints_every_new_metric`
closes its proxy-cell case with "below the statement: no trace, no phases"
(no `stmt_phase_*` counter moves in `snb-sf100-proxy.go3`).  Since PR 39 a
device statement that enters at `TpuRuntime.traverse` with no trace active
is rooted by the runtime itself (ISSUE 39's tentpole), so that case cannot
pass, and only a `benchmark` PR may rewrite it.  Until one does, the case
is expected to fail; `test_statement_root.py` holds the proxy cell to
everything else the case held (every PR-24 metric of the cell printed, the
parts of `dispatch.hostside_ms`) and to the new truth (one root a
statement).  The `benchmark` PR that rewrites the assertion deletes this
file."""
import pytest

ENDED_BY_PR_39 = "test_a_traced_rehearsal_prints_every_new_metric[snb-sf100-proxy.go3]"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == ENDED_BY_PR_39:
            item.add_marker(pytest.mark.xfail(
                reason="asserts that the proxy cell opens no trace root; PR 39 opens one "
                       "(tests/benchmark/conftest.py)", strict=False))
