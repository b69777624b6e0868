#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of BENCHMARK.json, in ONE
process that holds the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from the seed, warms up (one replay of the
cell's whole request list), measures a closed-loop window of `--seconds`,
compares every request's last reply with the plain reference, and prints
one JSON object as the last line of stdout: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with `--trace 0`, its
per-layer metrics with `--trace 1`), `device`, and `breakdown` in a traced
run.  Everything else worth reading goes on earlier lines.

Without a TPU the run exits 2 and prints no result.  `--rehearse` runs the
same code at the configuration's `rehearse` sizes on whatever platform jax
has; its line always says `"correct": false` (with what the checks found
under `rehearsal`), so it can never pass for a chip result.

Every piece that belongs to one cell is found by the name the data gives
it: configs/<config>.json, traffic/<mix>.json, builders/<builder>.py,
drivers/<driver>.py, layers/<metric>.py, end_to_end/<metric>.py,
reference/ops/<op>.py.  See benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time

_T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


from benchmarks.lib import trace as T  # noqa: E402 — after the path is set


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


class TraceSlice:
    """Driver hooks that wrap a short slice of the window in a profiler
    trace: started at the first statement boundary after `start_after_s`,
    stopped at the first statement end after `max_stmts` traced statements
    or `max_s` seconds.  Each statement sent inside the slice is one
    `bench:stmt` span in the trace."""

    def __init__(self, trace_dir, t0, start_after_s, max_s, max_stmts):
        import jax
        self.profiler = jax.profiler
        self.dir, self.t0 = trace_dir, t0
        self.start_after_s, self.max_s, self.max_stmts = start_after_s, max_s, max_stmts
        self.lock = threading.Lock()
        self.state, self.done = "idle", 0
        self.t_on = self.t_off = None
        self.spans = threading.local()

    def before(self, sid, req):
        prof = self.profiler
        with self.lock:
            if self.state == "idle" and time.perf_counter() - self.t0 >= self.start_after_s:
                opts = prof.ProfileOptions()
                opts.python_tracer_level = 0
                prof.start_trace(self.dir, profiler_options=opts)
                with prof.TraceAnnotation(T.SLICE_BEGIN):
                    pass
                self.state, self.t_on = "on", time.perf_counter()
            on = self.state == "on"
        if on:
            span = prof.TraceAnnotation(T.STMT, idx=req["idx"])
            span.__enter__()
            self.spans.span = span

    def after(self, sid, req, rec):
        span = getattr(self.spans, "span", None)
        if span is not None:
            span.__exit__(None, None, None)
            self.spans.span = None
        stop_here = False
        with self.lock:
            if self.state != "on":
                return
            if rec.t_send >= self.t_on:
                self.done += 1
            if self.done >= self.max_stmts or time.perf_counter() - self.t_on >= self.max_s:
                with self.profiler.TraceAnnotation(T.SLICE_END):
                    pass
                self.t_off = time.perf_counter()
                self.state = "stopping"
                stop_here = True
        if stop_here:
            self.profiler.stop_trace()
            self.state = "done"

    def finish(self):
        """Stop a trace the window closed on."""
        if self.state == "on":
            self.t_off = time.perf_counter()
            self.profiler.stop_trace()
            self.state = "done"


def load_manifest():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_for(manifest, group, cell):
    """The metrics of `group` that this cell reports."""
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


def main(argv=None, wrap_session=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny sizes on any platform; never correct")
    ap.add_argument("--control", default=None,
                    help="after the check, put a named broken answer (controls/<name>.py) in "
                         "the program's place and report that the check refuses it")
    a = ap.parse_args(argv)
    t_start = _T0 if argv is None else time.perf_counter()

    manifest = load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if a.workload not in cells:
        print(f"run.py: no workload {a.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = cells[a.workload]

    from benchmarks.lib import arith, loader
    from benchmarks.lib.compiles import CompileWatch
    from benchmarks.lib.requests import make_requests, op_module
    from benchmarks.reference.graph import RefGraph

    cfg = loader.data("configs", cell["config"])
    mix = loader.data("traffic", cell["traffic"])
    sizes = cfg["rehearse"] if a.rehearse else cfg["sizes"]
    float_limit = float(cfg["limits"]["float_rel_gap"])
    if a.rehearse and "requests" in sizes:      # a rehearsal may shorten the list too
        mix["requests"] = min(int(mix["requests"]), int(sizes["requests"]))

    import jax

    from nebula_tpu.tpu.device import device_identity, enable_compile_cache
    from nebula_tpu.utils.stats import stats

    device = device_identity()
    if not a.rehearse and (device["platform"] != "tpu" or device["count"] < cell["chips"]):
        print(f"run.py: {a.workload} needs {cell['chips']} TPU chip(s); jax found "
              f"{device['count']} x {device['platform']!r} ({device['kind']}). "
              f"--rehearse runs the tiny sizes anyway.", file=sys.stderr)
        return 2
    peaks = arith.peaks_for(device["kind"]) if device["platform"] == "tpu" else None
    say(f"cell {a.workload}: config {cell['config']}, traffic {cell['traffic']}, seed {a.seed}, "
        f"window {a.seconds:g}s, trace {a.trace}" + (", REHEARSAL" if a.rehearse else ""))
    say(f"device: {json.dumps(device)}; jax {jax.__version__}; sizes {json.dumps(sizes)}; "
        f"departs from its source in {cfg['reduced']} (the configuration's file says why)")

    watch = CompileWatch()
    cache_dir = enable_compile_cache()

    def n_entries():
        with contextlib.suppress(OSError):
            return len(os.listdir(cache_dir))
        return 0
    entries0 = n_entries()
    say(f"compile cache: {cache_dir} ("
        f"{'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'checkout default'}"
        f"), {entries0} entries before")
    from nebula_tpu import native
    say(f"native/libnebula_native.so (built from source on first use): "
        f"{'in use' if native.available() else 'NOT available, Python paths serve'}")
    base = stats().snapshot()
    stages, reference_s = {}, 0.0

    # -- data from the seed; the reference is built from it before any
    # program state exists, and its seconds are kept out of setup_s
    t = time.perf_counter()
    tables = loader.module("reference/generators",
                           cfg["reference"]["generator"]).generate(sizes, a.seed)
    stages["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ref = RefGraph(tables, cfg["reference"]["dedupe_last"])
    requests = make_requests(mix, ref, a.seed)
    reference_s += time.perf_counter() - t
    say(f"data: {tables['n']} vertices, " + ", ".join(
        f"{int(e['src'].size)} {et} rows ({ref.n_edges(et)} edges)"
        for et, e in tables["edges"].items())
        + f" in {stages['data_s']:.1f}s; {len(requests)} requests, reference rows "
        f"{min(r['rows'] for r in requests)}..{max(r['rows'] for r in requests)} "
        f"(sum {sum(r['rows'] for r in requests)})")

    builder = loader.module("builders", cfg["builder"])
    driver = loader.module("drivers", mix["driver"])
    dep = builder.build(cfg, sizes, tables, say)
    stages.update(dep.stages)
    sessions, trace_dir = [], None
    try:
        for _ in range(int(mix["sessions"])):
            s = dep.open_session()
            sessions.append(wrap_session(s) if wrap_session else s)

        # -- warm-up: the first statement apart (export, pin, first
        # compile), then one replay of the whole list by every session
        m0 = (watch.compiles, watch.compile_s)
        t = time.perf_counter()
        # the LAST request goes first.  It is the list's heaviest
        # (lib/requests.py), so the runtime's edge budgets climb their
        # ladder here, once and straight to where they stay; and the replay
        # (and the window) meets the list's wrap-around pair too: the
        # runtime's speculative fetch slices by the previous statement's
        # kept size, and a pair not met in warm-up compiles
        # jit(dynamic_slice) inside the window
        first = sessions[0].execute(requests[-1])
        stages["first_statement_s"] = time.perf_counter() - t
        t = time.perf_counter()
        warm, warm_last, _, _ = driver.run(sessions, requests,
                                           rounds=int(mix.get("warmup_rounds", 1)))
        stages["replay_s"] = time.perf_counter() - t
        stages["compile_s"] = watch.compile_s - m0[1]
        sent = 1 + len(warm)
        warm_bad = [r for r in warm if not r.ok] + ([] if first.error is None else [first])
        retries = sum(getattr(r.stats, "retries", 0) for r in warm if r.stats is not None)
        say(f"warm-up: first statement {stages['first_statement_s']:.1f}s, replay of "
            f"{len(warm)} in {stages['replay_s']:.1f}s, {watch.compiles - m0[0]} backend "
            f"compiles ({stages['compile_s']:.1f}s), escalation retries {retries}, "
            f"{len(warm_bad)} failed" + (f": {warm_bad[0].error}" if warm_bad else ""))

        # -- the window
        hooks = None
        if a.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # a short slice of the window: after its first moments, for a
            # few seconds or a few statements, never more than half of it
            hooks = TraceSlice(trace_dir, time.perf_counter(), min(1.0, a.seconds / 4),
                               min(float(mix.get("trace_seconds", 3.0)), a.seconds / 2),
                               int(mix.get("trace_statements", 10 ** 9)))
        c0, k0 = stats().snapshot(), watch.compiles
        setup_s = time.perf_counter() - t_start - reference_s
        say("set-up stages: " + ", ".join(f"{k} {v:.1f}" for k, v in stages.items())
            + f"; reference (not in setup_s) {reference_s:.1f}s; setup_s {setup_s:.1f}")
        recs, last, w0, w1 = driver.run(sessions, requests, seconds=a.seconds, hooks=hooks,
                                        whole_rounds=bool(mix.get("whole_rounds", False)))
        if hooks is not None:
            hooks.finish()
        c1, compiles_in_window = stats().snapshot(), watch.compiles - k0
        sent += len(recs)
        inwin = [r for r in recs if r.in_window]
        elapsed = w1 - w0
        say(f"window: {len(inwin)} statements in {elapsed:.3f}s ({len(recs) - len(inwin)} more "
            f"in flight at the close), {sum(not r.ok for r in inwin)} failed, "
            f"{compiles_in_window} backend compiles inside")
        per = {}
        for r in inwin:
            per.setdefault(r.idx, []).append(r)
        for i in sorted(per)[:8]:
            rs = per[i]
            dev = [r.stats.device_s for r in rs if r.stats is not None]
            say(f"  request {i} ({requests[i]['template']['name']} from {requests[i]['start']}, "
                f"{requests[i]['rows']} rows): {len(rs)} x, mean "
                f"{1e3 * sum(r.latency_s() for r in rs) / len(rs):.1f} ms"
                + (f", device_s {sum(dev) / len(dev):.3f}, e_cap {rs[-1].stats.e_cap}, "
                   f"retries {sum(r.stats.retries for r in rs if r.stats is not None)}"
                   if dev else ""))
        ts = [r.stats for r in inwin if r.stats is not None]
        if ts:
            say("TraverseStats means over the window: " + ", ".join(
                f"{f} {sum(getattr(t, f) for t in ts) / len(ts):.3f}"
                for f in ("put_s", "device_s", "fetch_s", "mat_s", "queue_s", "total_s"))
                + f"; retries {sum(t.retries for t in ts)}; e_cap {ts[-1].e_cap}; "
                f"edges per statement {sum(t.edges_traversed() for t in ts) // len(ts)}")
        for r in [r for r in inwin if not r.ok][:3]:
            say(f"  failed: request {r.idx} -> {r.error or f'{r.n_rows} rows'}, "
                f"reference {requests[r.idx]['rows']}")

        # -- the check, outside the window: every request's last reply
        # (the window's, else the warm-up's) against the reference
        t = time.perf_counter()
        mismatched, gap, compared_rows, bad = 0, 0.0, 0, []
        control = {"name": a.control, "mismatched": 0, "float_rel_gap": 0.0} if a.control else None
        for req in requests:
            reply = last.get(req["idx"]) or warm_last.get(req["idx"])
            op = op_module(req["template"]["op"])
            want = op.answer(ref, req["template"], req["start"])
            if reply is None or reply.error is not None:
                n, g, detail = max(req["rows"], 1), None, f"no reply: {reply and reply.error}"
            else:
                n, g, detail = op.compare(reply, want)
            compared_rows += req["rows"]
            gap = max(gap, g or 0.0)
            if n or (g or 0.0) > float_limit:
                mismatched += abs(n)
                bad.append(f"request {req['idx']} ({req['template']['name']} from "
                           f"{req['start']}): {detail}")
            if control is not None:
                broken = loader.module("controls", a.control).broken(want)
                if broken is not None:
                    cn, cg, _ = op.compare(broken, want)
                    control["mismatched"] += abs(cn)
                    control["float_rel_gap"] = max(control["float_rel_gap"], cg or 0.0)
        check_s = time.perf_counter() - t
        reference_s += check_s
        fb = {k: v - base.get(k, 0) for k, v in c1.items()
              if k.startswith("tpu_host_fallback") and v > base.get(k, 0)}
        runs = int(c1.get("tpu_kernel_runs", 0) - base.get("tpu_kernel_runs", 0))
        checks = {
            "rows_mismatched": [mismatched, 0],
            "float_rel_gap": [gap, float_limit],
            "warmup_failed": [len(warm_bad), 0],
            "tpu_host_fallback_moved": [int(sum(fb.values())), 0],
            "device_statements_without_kernel_run": [max(sent - runs, 0), 0],
        }
        for k, (v, lim) in checks.items():
            say(f"check {k}: {v:g} (limit {lim:g})")
        say(f"check: {len(requests)} requests, {compared_rows} reference rows compared in "
            f"{check_s:.1f}s; tpu_kernel_runs +{runs} for {sent} device statements"
            + (f"; fallbacks {json.dumps(fb)}" if fb else ""))
        for line in bad[:5]:
            say(f"  DIFFERS {line}")
        if control is not None:
            control["correct"] = control["mismatched"] == 0 and \
                control["float_rel_gap"] <= float_limit
            say(f"control {a.control}: {control['mismatched']} rows differ (limit 0), float gap "
                f"{control['float_rel_gap']:.3e} (limit {float_limit:g}) -> correct "
                f"{str(control['correct']).lower()}")
        passed = all(v <= lim for v, lim in checks.values())

        # -- metrics
        reduced = None
        if a.trace and hooks.state == "done":
            xplane = T.find_xplane(trace_dir)
            reduced = T.reduce(T.load(xplane), sessions=len(sessions))
            say(f"trace: {os.path.getsize(xplane):,} bytes, planes {reduced['planes']}; slice "
                f"{reduced['window_s']:.3f}s, device busy {reduced['busy_s']:.4f}s")
        elif a.trace:
            say("trace: the slice never started (the window closed inside its first second)")

        def counter(name):
            return c1.get(name, 0) - c0.get(name, 0)
        ctx = {
            "records": recs, "window": inwin, "elapsed_s": elapsed, "setup_s": setup_s,
            "counter": counter, "served": dep.served,
            "tstats": ts,
            "compiles_in_window": compiles_in_window, "trace": reduced,
            "traced": [] if not (a.trace and hooks.t_off) else
            [r for r in recs if r.t_send >= hooks.t_on and r.t_done <= hooks.t_off],
            "peaks": peaks, "requests": requests, "schema": cfg["fixes"]["schema"],
        }
        group, kind = ("per_layer", "layers") if a.trace else ("end_to_end", "end_to_end")
        # a number read off the CPU backend never stands under a device
        # metric's name: a rehearsal keeps those apart
        on_chip = device["platform"] == "tpu"
        metrics, off_chip = {}, {}
        for m in metrics_for(manifest, group, a.workload):
            v = loader.module(kind, m["name"]).read(ctx)
            if v is not None:
                (metrics if on_chip or m["source"] != "device_trace" else off_chip)[
                    m["name"]] = {"value": v, "unit": m["unit"]}
        peak = max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in jax.devices()), default=0)
        dev_out = dict(device, memory_peak_bytes=peak)
        out = {"correct": bool(passed and device["platform"] == "tpu" and not a.rehearse),
               "attempted": len(inwin), "failed": sum(not r.ok for r in inwin),
               "metrics": metrics, "device": dev_out}
        if reduced is not None:
            dev_out["busy_s"], dev_out["window_s"] = reduced["busy_s"], reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
        if a.rehearse:
            out["rehearsal"] = {"checks_passed": bool(passed),
                                "cpu_backend_readings": off_chip}
        if control is not None:
            out["control"] = control
        say(f"compile cache: {n_entries()} entries after ({entries0} before); persistent-cache "
            f"hits {watch.cache_hits}, misses {watch.cache_misses}; {watch.compiles} backend "
            f"compiles, {watch.compile_s:.1f}s; reference {reference_s:.1f}s in all")
    finally:
        for s in sessions:
            with contextlib.suppress(Exception):
                s.close()
        dep.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
