#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on; it refuses any platform
but the TPU (a CPU rehearsal at toy size is selftest.py's business and
prints no device metric). The harness is driven by data: the cell names
a configuration and a traffic mix, `configs/<config>.json` names its
builder, `traffic/<traffic>.json` its driver (and, where the driver
needs the deployment installed another way, a builder of its own),
`metrics/<metric>.json` the reader of one per-layer metric; a driver
may bring its own service, control, counters and device-side checks
(README.md). The last line of
standard output is the result; the last lines of standard error are the
numbers `correct` was decided from, each beside its limit.
"""
from __future__ import annotations

import time

T_START_NS = time.perf_counter_ns()     # set-up is counted from here

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import program  # noqa: E402  (touches vproxy_tpu only inside functions)
import tracered  # noqa: E402

TRACE_MAX_S = 3.0       # the profiler covers this much of the window
QUEUE_WAIT_SAMPLE = 64  # traced run: 1 submit in N carries a trace id


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                     f"(has: {[w['name'] for w in bench['workloads']]})")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def device_info(require_tpu: bool, chips: int) -> tuple:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        print(f"run.py: found platform={info['platform']!r} "
              f"kind={info['kind']!r} count={len(devs)}; this cell needs "
              f"{chips} TPU chip(s) — nothing run", file=sys.stderr)
        raise SystemExit(3)
    return devs, info


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Profiler:
    """jax.profiler over the first TRACE_MAX_S of the window, host spans
    at TraceMe level and no Python tracer (it would record every call
    of a loop that makes millions)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False
        self.t0_ns = self.t1_ns = 0     # host clock (perf_counter_ns)
        self._win = None
        self._stopper = None
        self._lock = threading.Lock()

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._win = jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN)
        self._win.__enter__()
        self.t0_ns = time.perf_counter_ns()
        self.on = True

    def stop(self) -> None:
        import jax
        with self._lock:
            if not self.on:
                return
            self.t1_ns = time.perf_counter_ns()
            self._win.__exit__(None, None, None)
            self.on = False
            jax.profiler.stop_trace()

    def tick(self) -> None:
        """Past TRACE_MAX_S, stop off the main thread: writing the trace
        out takes seconds, and the window has to close on time."""
        if self.on and self._stopper is None and \
                time.perf_counter_ns() - self.t0_ns >= TRACE_MAX_S * 1e9:
            self._stopper = threading.Thread(target=self.stop)
            self._stopper.start()

    def reduce(self) -> dict:
        if self._stopper is not None:
            self._stopper.join()
        return tracered.load_xplane(tracered.find_xplane(self.dir))

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class GcLog:
    """Python garbage collections as `gc.callbacks` reports them: a
    pause of the whole process, so a run that reads far off can be held
    against it."""

    def __init__(self):
        import gc
        self.events: list = []      # (start perf_counter_ns, gen, ns)
        self._t0 = 0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        else:
            self.events.append((self._t0, info["generation"],
                                time.perf_counter_ns() - self._t0))

    def between(self, t0_ns: int, t1_ns: int) -> dict:
        out: dict = {}
        for t, gen, ns in self.events:
            if t0_ns <= t < t1_ns:
                c = out.setdefault(f"gen{gen}", [0, 0.0, 0.0])
                c[0] += 1
                c[1] += ns / 1e6
                c[2] = max(c[2], ns / 1e6)
        return {k: [v[0], round(v[1], 1), round(v[2], 1)]
                for k, v in out.items()}


def longest_gaps(win, k: int = 3) -> list:
    """The k longest stretches of the window without a delivery:
    [(ms, seconds after the window opened)]."""
    import numpy as np
    t = np.sort(win.t_done[(win.t_done >= win.t_open)
                           & (win.t_done < win.t_close)])
    if len(t) < 2:
        return []
    gaps = np.diff(t)
    top = np.argsort(gaps)[-k:][::-1]
    return [(round(float(gaps[i]) / 1e6, 1),
             round(float(t[i] - win.t_open) / 1e9, 2)) for i in top]


def compare(plan, win, want) -> tuple:
    """Every verdict the loop delivered against the reference, by pool
    rank. -> (number wrong, first few (query, got, want))."""
    delivered = win.t_done > 0
    bad = delivered & (win.got != want[win.rank]).any(axis=1)
    first = []
    for k in bad.nonzero()[0][:5]:
        r = int(win.rank[k])
        first.append({"query": repr(plan.pool[r]), "rank": r,
                      "got": win.got[k].tolist(),
                      "want": want[r].tolist()})
    return int(bad.sum()), first


# ---- what a driver may bring in place of run.py's own (README.md)

# run.py's own device-side checks, for a driver that brings none: the
# ClassifyService's counters. name -> what it is for: "device" = the
# proof that the device served, "host" = answered on the host
SERVICE_CHECKS = {"answered_by_host_oracle": "host", "failovers": "other",
                  "not_answered_by_device": "device"}
KEPT = ("wrong_verdicts", "undelivered")    # run.py's, never a driver's


def service_checks(totals: dict, submitted: int) -> dict:
    return {
        "answered_by_host_oracle": [totals["oracle_queries"], 0],
        "failovers": [totals["failovers"], 0],
        "not_answered_by_device": [submitted - totals["device_queries"], 0],
    }


def declared_checks(driver) -> dict:
    """name -> role of the device-side checks this driver's runs are
    held to. Refuses, before anything is driven, a driver whose own
    checks carry no proof that the device served, or that names one of
    run.py's: "the device served it" is a guarantee of every
    configuration file."""
    if not hasattr(driver, "checks"):
        return SERVICE_CHECKS
    roles = dict(getattr(driver, "CHECKS", {}))
    if "device" not in roles.values():
        raise SystemExit(
            f"run.py: driver {driver.__name__} brings its own checks "
            f"{sorted(roles)} and none of them is declared the proof that "
            f"the device served (CHECKS[name] = \"device\") — nothing run")
    taken = [k for k in roles if k in KEPT]
    bad = sorted(set(roles.values()) - {"device", "host", "other"})
    if taken or bad:
        raise SystemExit(f"run.py: driver {driver.__name__} CHECKS: {taken} "
                         f"are run.py's own; unknown roles {bad}")
    return roles


def device_side(driver, roles: dict, dep, svc, plan, win, totals) -> dict:
    """The device-side checks of this run, each [value, limit]: the
    driver's own, held to what it declared (the same names, a limit of
    0 on every proof that the device served), else the service's."""
    if not hasattr(driver, "checks"):
        return service_checks(totals, win.n)
    got = driver.checks(dep, svc, plan, win)
    if list(got) != list(roles):
        raise SystemExit(f"run.py: driver {driver.__name__} declared the "
                         f"checks {list(roles)} and returned {list(got)}")
    loose = [k for k, (_v, lim) in got.items()
             if roles[k] != "other" and lim != 0]
    if loose:
        raise SystemExit(f"run.py: {loose} count lookups the device did "
                         f"not serve: their limit is 0")
    return got


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, overrides: dict | None = None,
             control: bool = False, before_window=None) -> dict:
    """-> the result object. overrides (selftest, tests): replacement
    `sizes` / traffic parameters for a toy run. control: the control
    stands in the program's place. before_window(svc): test hook, runs
    with the service just before the loop starts (faults are planted
    there)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, workload)
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if overrides:
        config["sizes"].update(overrides.get("sizes", {}))
        traffic.update(overrides.get("traffic", {}))

    program.apply_operator_settings(config)
    devs, dev_info = device_info(require_tpu, cell["chips"])
    cache = program.compile_cache()
    clog = program.CompileLog()
    errlog = program.ErrorLog()
    gclog = GcLog()
    say(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)} "
        f"device {dev_info} compile-cache {cache}")

    builder = importlib.import_module(
        "builders." + traffic.get("builder", config["builder"]))
    driver = importlib.import_module("drivers." + traffic["driver"])
    roles = declared_checks(driver)
    read_counters = getattr(driver, "counters", program.counters)
    t0 = time.monotonic()
    dep = builder.build(config, seed)
    plan = driver.Plan(dep, traffic, seed, seconds)
    t_gen = time.monotonic() - t0
    if control:
        import control as control_mod
        svc = getattr(driver, "control_service",
                      control_mod.ControlService)(dep, plan, seed)
        say(f"CONTROL in the program's place: {svc.what}")
    else:
        t0 = time.monotonic()
        dep.install()
        t_install = time.monotonic() - t0
        t0 = time.monotonic()
        mark = len(clog.compiles)
        n_warm = driver.warm(dep, plan,
                             program.pad_buckets(traffic["outstanding"]))
        warm = clog.compiles[mark:]
        say(f"set-up: rules+pool {t_gen:.1f}s, install {t_install:.1f}s "
            f"{ {k: round(v, 1) for k, v in dep.install_s.items()} }, "
            f"backends {dep.backends()}, table bytes {dep.table_bytes()}, "
            f"warm-up {time.monotonic() - t0:.1f}s: {n_warm} dispatches, "
            f"{len(warm)} compile requests "
            f"{sum(s for _t, _n, s in warm):.1f}s "
            f"(slowest {max((s for _t, _n, s in warm), default=0):.1f}s)")
        svc = driver.service(dep, plan) if hasattr(driver, "service") \
            else program.new_service()
    if before_window is not None:
        before_window(svc)

    prof = Profiler() if trace else None
    specs = {m["name"]: load_json(HERE, "metrics", m["name"] + ".json")
             for m in bench["per_layer"] if applies(m, workload)}
    inst = program.Instrument(svc, QUEUE_WAIT_SAMPLE) \
        if trace and not control else None
    t_mono_open = [0.0, 0.0]

    def on_open() -> None:
        if prof is not None:
            prof.start()
        t_mono_open[0] = time.monotonic()

    def on_tick() -> None:
        if prof is not None:
            prof.tick()
        if inst is not None:
            inst.drain()

    def on_close() -> None:
        t_mono_open[1] = time.monotonic()
        if prof is not None:
            prof.stop()

    try:
        win = driver.drive(dep, svc, plan, seconds,
                           lambda: read_counters(svc), on_open, on_tick,
                           on_close, instrument=inst)
    finally:
        if inst is not None:
            inst.close()
    setup_s = (win.t_open - T_START_NS) / 1e9
    totals = read_counters(svc)
    in_window = clog.between(t_mono_open[0], t_mono_open[1])
    svc.close()
    peak = memory_peak(devs)
    dep.matchers.clear()

    # ---- the earlier lines: everything a wrong verdict is read from
    say(f"service counters (ramp+window+drain): {totals}")
    say(f"last_failover: {totals.get('last_failover', '')!r}")
    if getattr(win, "error", ""):
        say(f"the driver's loop died: {win.error}")
    say(f"compiles before the window: {len(clog.compiles) - len(in_window)}; "
        f"in the window: {len(in_window)} {in_window[:8]}")
    say(f"garbage collections in the window [count, total ms, longest ms]: "
        f"{gclog.between(win.t_open, win.t_close)}; longest stretches "
        f"without a delivery (ms, at s): {longest_gaps(win)}")
    say(f"delivered in each second of the window: "
        f"{driver.rate_by_second(win)}")
    for line in errlog.lines:
        say(f"program log: {line}")
    errlog.close()

    # ---- correct: every delivered verdict against the plain reference
    t0 = time.monotonic()
    want = dep.answers(plan.pool)
    wrong, first = compare(plan, win, want)
    reference_s = time.monotonic() - t0
    for f in first:
        say(f"WRONG: {f}")
    submitted = win.n
    checks = {"wrong_verdicts": [wrong, 0],
              "undelivered": [win.undelivered, 0]}
    checks.update(device_side(driver, roles, dep, svc, plan, win, totals))
    correct = all(0 <= v <= lim for v, lim in checks.values())
    failed = min(submitted, wrong + win.undelivered + sum(
        checks[k][0] for k, role in roles.items() if role == "host"))
    e2e = driver.end_to_end(win)
    say(f"window {e2e['_window_s']:.3f}s: submitted {submitted} "
        f"(window+ramp), delivered in window {e2e['_delivered_in_window']}, "
        f"latency samples {e2e['_latency_samples']}, no-match share of "
        f"pool {float((want[:, 0] < 0).mean()):.3f}, reference "
        f"{reference_s:.1f}s, set-up {setup_s:.1f}s")

    device = dict(dev_info, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": submitted, "failed": failed}
    metrics: dict = {}
    if not trace:
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if applies(m, workload):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        tr = prof.reduce()
        prof.close()
        ctx = SimpleNamespace(      # what a per-layer reader may read
            win=win, plan=plan, dep=dep, trace=tr, counters_open=win.counters_open,
            counters_close=win.counters_close, spans=inst.totals if inst else {},
            queue_wait_us=inst.queue_wait_us if inst else [],
            compiles_in_window=in_window, device_kind=dev_info["kind"],
            trace_t0_ns=prof.t0_ns, trace_t1_ns=prof.t1_ns,
            peaks=load_json(HERE, "peaks.json"))
        window_s = (prof.t1_ns - prof.t0_ns) / 1e9
        busy_s = tracered.busy_seconds(tr)
        device.update(busy_s=busy_s, window_s=window_s)
        for m in bench["per_layer"]:
            if not applies(m, workload):
                continue
            spec = specs[m["name"]]
            reader = importlib.import_module("readers." + spec["reader"])
            value = reader.read(ctx, spec.get("params", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        win_ev = [s for s in tracered.host_spans(tr)
                  if s[2] == tracered.WINDOW_SPAN]
        if win_ev:
            result["breakdown"] = {
                "device_ops": tracered.top_device_ops(tr),
                "idle_gaps": tracered.idle_gaps(tr, win_ev[0][0],
                                                win_ev[0][1])}
        say(f"traced {window_s:.3f}s of the window: device busy "
            f"{busy_s:.4f}s; programs {tracered.programs(tr)}; host spans "
            f"{ {k: [v[0], round(v[1] / 1e9, 3), v[2]] for k, v in ctx.spans.items()} }")
    result.update(metrics=metrics, device=device)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"compared {k}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the cell's control in the program's place; "
                         "`correct` has to come out false")
    a = ap.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      control=a.control)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
