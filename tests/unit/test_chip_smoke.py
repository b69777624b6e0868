"""chip_smoke.py rehearsed in-process on the CPU backend, and the
device→host fallback counter it fails on.

The real proof is `python chip_smoke.py` on a TPU (one process per
chip); here the same phases run at a tiny size to keep wrong paths and
shardings out of a chip call, and the script must still refuse to call
a platform other than `tpu` ok."""
import json

import pytest

import chip_smoke
from nebula_tpu.bench.datagen import make_social_graph
from nebula_tpu.exec.engine import QueryEngine
from nebula_tpu.tpu import TpuRuntime, make_mesh
from nebula_tpu.tpu.device import TpuUnavailable
from nebula_tpu.utils.stats import stats


def test_phases_a_and_b_rehearse_equal_but_never_ok_off_tpu(capsys):
    rc = chip_smoke.main([
        "--rehearse", "--small-persons", "300", "--small-degree", "6",
        "--persons", "3000", "--degree", "6", "--seeds", "4",
        "--pagerank-iters", "3"])
    out = capsys.readouterr().out
    lines = out.strip().splitlines()
    last = json.loads(lines[-1])
    # every phase ran and every comparison was equal ...
    assert "=== phase a done" in out and "=== phase b done" in out
    assert " checks, 0 failed" in out, out[-4000:]
    assert "FAIL" not in out, out[-4000:]
    assert "every phase ran — ['a', 'b']" in out
    # ... and the script still reports not-ok: this is not a TPU
    assert rc != 0
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_without_rehearse_a_non_tpu_platform_prints_no_result(capsys):
    rc = chip_smoke.main([])
    cap = capsys.readouterr()
    assert rc != 0
    assert cap.out.strip() == ""
    assert "not a TPU" in cap.err


def test_device_dispatch_failure_is_served_by_host_and_counted(
        monkeypatch):
    """A device dispatch that raises is answered by the host engine
    with the same rows (never wrong, only absent) — and is COUNTED, so
    chip_smoke.py's fallback check fails instead of passing on rows
    that came from Python."""
    store = make_social_graph(n_persons=60, avg_degree=4, parts=1,
                              seed=5, space="fb")
    q = "GO 2 STEPS FROM 1, 2, 3 OVER KNOWS YIELD dst(edge) AS d"

    def rows(engine):
        s = engine.new_session()
        assert engine.execute(s, "USE fb").error is None
        rs = engine.execute(s, q)
        assert rs.error is None, rs.error
        return sorted(map(repr, rs.data.rows))

    want = rows(QueryEngine(store))
    assert want

    def boom(self, *a, **kw):
        raise TpuUnavailable("injected: the device refused the dispatch")
    monkeypatch.setattr(TpuRuntime, "traverse", boom)
    key = "tpu_host_fallback{error=TpuUnavailable,site=traverse}"
    before = stats().snapshot().get(key, 0)
    eng = QueryEngine(store, tpu_runtime=TpuRuntime(make_mesh(1)))
    assert rows(eng) == want
    assert stats().snapshot().get(key, 0) == before + 1
    assert "TpuUnavailable" in eng.qctx.last_tpu_fallback
    # what chip_smoke.py's final check reads
    assert chip_smoke.counted_fallbacks().get(key, 0) >= 1


@pytest.mark.parametrize("explicit_cpu", [False, True])
def test_require_tpu_refuses_a_host_backend_nobody_asked_for(
        monkeypatch, explicit_cpu):
    """`daemons.py graphd --tpu` starts through require_tpu: off-TPU it
    fails unless the operator set JAX_PLATFORMS=cpu on purpose."""
    from nebula_tpu.tpu.device import require_tpu
    if explicit_cpu:
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert require_tpu("test")["platform"] == "cpu"
    else:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="not 'tpu'"):
            require_tpu("test")


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and no directory is set in code;
    unset, the cache is <checkout>/.jax_cache — a fixed path."""
    import os

    import jax

    from nebula_tpu.tpu import device
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "/some/dir")
        assert device.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "/some/dir"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        assert device.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
