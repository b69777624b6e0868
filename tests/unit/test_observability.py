"""Metrics registry, config layers, webservice endpoints, SHOW/UPDATE
CONFIGS, PROFILE device fields — the SURVEY §5 aux-subsystem surface."""
import json
import urllib.request

import pytest

from nebula_tpu.cluster.webservice import WebService
from nebula_tpu.exec import QueryEngine
from nebula_tpu.utils.config import Config, ConfigError, get_config
from nebula_tpu.utils.stats import StatsManager, stats


def test_stats_counters_and_series():
    sm = StatsManager()
    sm.inc("q")
    sm.inc("q", 4)
    sm.gauge("hbm", 123.0)
    for v in (10, 20, 30, 40):
        sm.add_value("lat", v)
    snap = sm.snapshot()
    assert snap["q"] == 5 and snap["hbm"] == 123.0
    assert snap["lat.count"] == 4 and snap["lat.avg"] == 25
    assert snap["lat.p50"] == 30
    assert "lat=..." not in sm.to_text()


def test_config_layers(tmp_path, monkeypatch):
    c = Config()
    c.define("alpha", 10, "t")
    c.define("beta", "x")
    assert c.get("alpha") == 10
    f = tmp_path / "conf"
    f.write_text("# comment\n--alpha=20\nbeta = y\n")
    c.load_file(str(f))
    assert c.get("alpha") == 20 and c.get("beta") == "y"
    monkeypatch.setenv("NEBULA_ALPHA", "30")
    assert c.get("alpha") == 30
    c.set_dynamic("alpha", 40)
    assert c.get("alpha") == 40
    with pytest.raises(ConfigError):
        c.get("nope")
    with pytest.raises(ConfigError):
        c.set_dynamic("nope", 1)


def test_config_bad_file_flag(tmp_path):
    c = Config()
    c.define("a", 1)
    f = tmp_path / "conf"
    f.write_text("zzz=1\n")
    with pytest.raises(ConfigError):
        c.load_file(str(f))


def test_webservice_endpoints():
    stats().inc("ws_test_counter", 7)
    get_config().define("ws_test_flag", 1, "t")
    ws = WebService(role="graphd")
    ws.start()
    try:
        base = f"http://{ws.addr}"
        st = json.loads(urllib.request.urlopen(base + "/status").read())
        assert st == {"status": "running", "role": "graphd"}
        body = urllib.request.urlopen(base + "/stats").read().decode()
        assert "ws_test_counter=7" in body
        flags = json.loads(urllib.request.urlopen(
            base + "/flags?format=json").read())
        assert flags["ws_test_flag"] == 1
        req = urllib.request.Request(base + "/flags", method="PUT",
                                     data=b"ws_test_flag=42")
        assert urllib.request.urlopen(req).status == 200
        assert get_config().get("ws_test_flag") == 42
        req = urllib.request.Request(base + "/flags", method="PUT",
                                     data=b"nosuch=1")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req)
    finally:
        ws.stop()


def test_show_and_update_configs():
    eng = QueryEngine()
    s = eng.new_session()
    r = eng.execute(s, "SHOW CONFIGS")
    assert r.ok
    names = [row[1] for row in r.data.rows]
    assert "slow_query_threshold_us" in names
    r = eng.execute(s, "UPDATE CONFIGS slow_query_threshold_us = 123456")
    assert r.ok, r.error
    assert get_config().get("slow_query_threshold_us") == 123456
    get_config().dynamic_layer.pop("slow_query_threshold_us", None)
    r = eng.execute(s, "UPDATE CONFIGS nosuchflag = 1")
    assert not r.ok


def test_put_flags_is_atomic():
    get_config().define("ws_atom_a", 1)
    get_config().define("ws_atom_b", 2)
    ws = WebService(role="t")
    ws.start()
    try:
        req = urllib.request.Request(
            f"http://{ws.addr}/flags", method="PUT",
            data=b"ws_atom_a=9\nnosuchflag=1\nws_atom_b=9")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req)
        # nothing applied — 400 means NO change
        assert get_config().get("ws_atom_a") == 1
        assert get_config().get("ws_atom_b") == 2
    finally:
        ws.stop()


def test_live_config_affects_slow_log():
    eng = QueryEngine()
    s = eng.new_session()
    get_config().set_dynamic("slow_query_threshold_us", 0)
    try:
        eng.execute(s, "YIELD 1")
        assert eng.slow_log, "live threshold change must take effect"
    finally:
        get_config().dynamic_layer.pop("slow_query_threshold_us", None)


def test_error_queries_counted():
    eng = QueryEngine()
    s = eng.new_session()
    before = stats().snapshot().get("num_query_errors", 0)
    eng.execute(s, "GOGO")                   # syntax error
    eng.execute(s, "GO FROM 1 OVER nosuch")  # semantic error
    after = stats().snapshot()
    assert after["num_query_errors"] >= before + 2


def test_query_metrics_flow():
    before = stats().snapshot().get("num_queries", 0)
    eng = QueryEngine()
    s = eng.new_session()
    eng.execute(s, "YIELD 1")
    eng.execute(s, "YIELD 2")
    after = stats().snapshot()
    assert after["num_queries"] >= before + 2
    assert after["query_latency_us.count"] >= 2
