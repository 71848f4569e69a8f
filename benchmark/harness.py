"""One run of one cell: set-up, the timed window, the check, the result.

Driven by data: the cell, its configuration and its traffic are found by
name in BENCHMARK.json; a query is ``queries/<name>.py``, a per-layer metric
``layer_metrics/<name>.py``, an end-to-end metric ``end_to_end/<name>.py``.
Nothing in this file names a cell, a query or a metric.

The entry the window drives is ``NetClient.submit()`` over loopback into
``QueryFrontend`` -> ``QueryServer`` -> planner -> device operators, with
server, front-end and clients as threads of this one process (a chip
belongs to one process). ``correct`` is decided on the Arrow tables those
timed requests returned (compare.py), after the window has closed.
"""

import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(1, ROOT)

import compare  # noqa: E402
import datagen  # noqa: E402
import meters  # noqa: E402
import spanred  # noqa: E402
import tracered  # noqa: E402
import trafficgen as traffic_mod  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_ROOT = os.path.join(ROOT, ".bench_cache")
MAX_WARM_ROUNDS = 12
ANSWER_WAIT_S = 60.0  # an answer may come this long after the close
JOURNAL_CAPACITY = 1 << 20


class NoChip(RuntimeError):
    """JAX found no TPU, fewer chips than the cell asks for, or a device
    kind that peaks.json does not know: no result is printed."""


def load_by_path(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by its name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[0]
    conf_entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        config = json.load(f)
    mix = traffic_mod.load(traffic_mod.traffic_path(ROOT, cell["traffic"]))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values (no interpolation)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s), found {info}")
    return info


def peaks_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def cache_dir(config_name: str, sf: float) -> str:
    """Where the program's own stores (exported programs, autotune timings)
    live for one configuration at one scale: a fixed place inside the
    checkout, as ``.jax_cache`` is, and never under the temp directory,
    where the two sides of a comparison would share them. One directory per
    configuration and scale because the export store's key leaves the
    shapes out."""
    return os.path.join(CACHE_ROOT, f"{config_name}_sf{float(sf):g}")


def primed_marker(workload: str, scale=None) -> str:
    """The file that says this checkout's caches hold the cell's programs
    (run.py writes it once a set-up has compiled nothing)."""
    spec = load_cell(workload)
    sf = spec["config"]["scale_factor"] if scale is None else scale
    return os.path.join(cache_dir(spec["cell"]["config"], sf),
                        f"primed.{workload}")


def program_conf(config: dict, mix: dict, stores: str):
    """The RapidsConf of server, front-end and clients: the program's
    defaults, the configuration's ``conf`` over them, and what the harness
    has to place: the tenants' tokens, the two stores, the journal."""
    from spark_rapids_tpu.config import conf as C
    values = dict(config["conf"])
    tenants = mix["tenants"]
    values[C.JIT_PERSIST_DIR.key] = os.path.join(stores, "jit_persist")
    values[C.AUTOTUNE_DIR.key] = os.path.join(stores, "autotune")
    values[C.NET_AUTH_TOKENS.key] = ",".join(
        f"tok-{t['name']}={t['name']}" for t in tenants)
    values[C.SERVE_FAIRSHARE_WEIGHTS.key] = ",".join(
        f"{t['name']}={t.get('weight', 1)}" for t in tenants)
    # the planner resets the journal's bound from the conf at every plan;
    # the harness reads the whole journal at the end (an eviction fails the
    # run), so the bound has to hold a window's events
    values[C.METRICS_JOURNAL_CAPACITY.key] = JOURNAL_CAPACITY
    return C.RapidsConf(values)


class Window:
    """The streams (one closed-loop client each) and what they recorded."""

    def __init__(self, mix, seed, host, port, conf, config, queries):
        self.mix, self.seed = mix, seed
        self.host, self.port, self.conf, self.config = host, port, conf, config
        self.queries = queries
        self.tenants = {t["name"]: t for t in mix["tenants"]}
        self.requests = []
        self._lock = threading.Lock()
        self._seq = 0
        self.clients = []

    def connect(self):
        """One connection per stream, opened once the first queries have
        run: the front-end reaps a session that sent no frame for
        net.session.idleTimeoutS (300 s), and a cold compile is longer."""
        for si, stream in enumerate(self.mix["streams"]):
            self.clients.append((si, stream) + self._connect(stream))

    def first_queries(self) -> dict:
        """{instance: wall s} of the first submit of each query with each
        set of parameters, on a connection of its own: upload, trace,
        compile or cache load."""
        first = {}
        for si, stream in enumerate(self.mix["streams"]):
            cl, dfs = self._connect(stream)
            try:
                for q in dict.fromkeys(stream["queries"]):
                    key = traffic_mod.instance_key(q, stream["params"].get(q))
                    if key in first:
                        continue
                    rec = self.send(si, stream, cl, dfs, q, keep=False)
                    if not rec["ok"]:
                        raise RuntimeError(f"first {key} failed: "
                                           f"{rec['error']}")
                    first[key] = rec["t1"] - rec["t0"]
            finally:
                cl.close()
        return first

    def _connect(self, stream):
        from spark_rapids_tpu.net import NetClient
        cl = NetClient(self.host, self.port, token=f"tok-{stream['tenant']}",
                       conf=self.conf,
                       shuffle_partitions=self.config["shuffle_partitions"],
                       timeout_s=1200)
        d = {k: cl.table(k, batch_rows=self.config["batch_rows"],
                         partitions=self.config["partitions"])
             for k in cl.server_tables}
        dfs = {}
        for q in stream["queries"]:
            p = stream["params"].get(q)
            dfs[q] = self.queries[q].build(d, p) if p else \
                self.queries[q].build(d)
        return cl, dfs

    def send(self, si, stream, cl, dfs, qname, annotate=False, keep=True):
        """One request; returns its record (also kept when ``keep``)."""
        import jax
        with self._lock:
            self._seq += 1
            name = f"r{self._seq}"
        rec = {"name": name, "query": qname, "stream": si,
               "key": traffic_mod.instance_key(
                   qname, stream["params"].get(qname)),
               "tenant": stream["tenant"], "ok": False,
               "table": None, "error": None}
        ann = (jax.profiler.TraceAnnotation(
            f"{tracered.SUBMIT}{qname}:{name}") if annotate else None)
        priority = int(self.tenants[stream["tenant"]].get("priority", 0))
        rec["t0"] = time.perf_counter()
        if ann:
            ann.__enter__()
        try:
            rec["table"] = cl.submit(dfs[qname], priority=priority,
                                     name=name, timeout_s=1200)
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001: counted as failed, never hidden
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            rec["t1"] = time.perf_counter()
            if ann:
                ann.__exit__(None, None, None)
        if keep:
            with self._lock:
                self.requests.append(rec)
        return rec

    def warm_round(self):
        """Every stream sends each of its queries once, all at once."""
        errs = []

        def one(si, stream, cl, dfs):
            for q in stream["queries"]:
                rec = self.send(si, stream, cl, dfs, q, keep=False)
                if not rec["ok"]:
                    errs.append(rec["error"])
        ths = [threading.Thread(target=one, args=c) for c in self.clients]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        if errs:
            raise RuntimeError(f"warm-up failed: {errs[0]}")

    def run_closed(self, seconds, annotate):
        start = time.perf_counter()
        deadline = start + seconds

        def loop(si, stream, cl, dfs):
            cycle = traffic_mod.stream_cycle(self.mix, self.seed, si)
            while time.perf_counter() < deadline:
                self.send(si, stream, cl, dfs, next(cycle),
                          annotate=annotate())
        ths = [threading.Thread(target=loop, args=c, daemon=True)
               for c in self.clients]
        for t in ths:
            t.start()
        return start, ths

    def close(self):
        for _, _, cl, _ in self.clients:
            cl.close()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, scale=None, require_chip=True,
             tamper=None, log=None, probe=None, keep_trace=None,
             prime=False) -> dict:
    """Runs the cell and returns the result object (the last line).

    ``scale`` overrides the configuration's scale factor (rehearsals and
    tests only: run.py never prints a result for such a run). ``tamper``
    is for the tests under tests/: a function (tables) -> tables applied to
    what the front-end is given. ``probe``, a dict, is handed the seed's
    raw columns and references (control.py reads the control from them).
    ``prime`` stops after set-up and returns what it compiled (run.py's
    children on a cold checkout).
    """
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    spec = load_cell(workload)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    sf = float(config["scale_factor"] if scale is None else scale)

    import jax
    import spark_rapids_tpu  # noqa: F401  (x64 on, compile cache placed)
    from spark_rapids_tpu.net import QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    from spark_rapids_tpu.utils import tracing

    device = device_info(int(cell["chips"]), require_chip)
    peaks = peaks_for(device["kind"]) if require_chip else None
    log(f"[bench] {workload} seed={seed} seconds={seconds} trace={int(trace)}"
        f" on {device['platform']} '{device['kind']}' x{device['count']}"
        f" sf={sf} cache={jax.config.jax_compilation_cache_dir}")
    meter = meters.CompileMeter()

    queries = {q: load_by_path("queries", q)
               for q in traffic_mod.query_names(mix)}
    needed = [t for t in config["tables"]
              if any(t in m.TABLES for m in queries.values())]
    missing = {t for m in queries.values() for t in m.TABLES} - set(needed)
    if missing:
        raise SystemExit(f"{cell['config']} has no table(s) {sorted(missing)}")

    # a build that lacks a capability the configuration ``requires``
    # refuses here, in seconds, and not after the data are made
    conf = program_conf(config, mix, cache_dir(cell["config"], sf))

    # -- set-up: data from the seed, upload, warm-up ------------------------
    t = time.perf_counter()
    raw = datagen.make(needed, sf, seed)
    tables = {name: datagen.arrow(cols) for name, cols in raw.items()}
    rows = {name: tb.num_rows for name, tb in tables.items()}
    stages = {"import_s": t - t_process, "data_s": time.perf_counter() - t}
    served = tamper(tables) if tamper else tables

    srv = QueryServer(conf)
    fe = QueryFrontend(srv, tables=served, host="127.0.0.1", port=0)
    win = Window(mix, seed, fe.host, fe.port, conf, config, queries)
    try:
        t = time.perf_counter()
        first = win.first_queries()
        stages["first_queries_s"] = time.perf_counter() - t
        win.connect()
        t = time.perf_counter()
        # autotune explores other paths once it has timed the first, so
        # rounds go on until one has compiled nothing
        for i in range(MAX_WARM_ROUNDS):
            before = meter.read()["programs"]
            win.warm_round()
            if i + 1 >= int(mix["warm_rounds"]) and (
                    meter.read()["programs"] == before):
                break
        stages["warm_s"] = time.perf_counter() - t
        stages["warm_rounds"] = i + 1
        warm_meter = meter.read()
        if prime:
            counters = meters.store_counters()
            return {"compiled": warm_meter["xla_cache_misses"],
                    "exported": counters["jit_persist_store_total"],
                    "programs": warm_meter["programs"], "stages_s": stages,
                    "program_counters": counters}

        # -- the window ----------------------------------------------------
        tracing.set_capture(bool(trace), clear=True)
        traced = {"on": False}
        setup_s = time.perf_counter() - t_process
        start, threads = win.run_closed(seconds, lambda: traced["on"])
        trace_info = None
        if trace:
            trace_info = _trace_window(mix, seconds, start, traced, log)
        for th in threads:
            th.join(timeout=max(0.0, start + seconds + ANSWER_WAIT_S
                                - time.perf_counter()))
        end = max([r["t1"] for r in win.requests] + [start + seconds])
        never = sum(1 for th in threads if th.is_alive())
        requests = sorted(list(win.requests), key=lambda r: r["t0"])
        spans = tracing.trace_events(clear=True) if trace else []
        tracing.set_capture(False, clear=True)
        in_window = meter.programs_between(start, end)
        mem = [d.memory_stats() or {} for d in jax.devices()]
        memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
        hidden = meters.hidden_device_evidence()
        counters = meters.store_counters()
    finally:
        win.close()
        fe.close()
        srv.close()
    # free the program's state before the reference runs
    del served, tables, win, fe, srv

    # -- the check, outside set-up and window ------------------------------
    t = time.perf_counter()
    instances = {}  # instance key -> (query name, parameters)
    for stream in mix["streams"]:
        for q in stream["queries"]:
            p = stream["params"].get(q)
            instances[traffic_mod.instance_key(q, p)] = (q, p)
    references = {k: (queries[q].reference(raw, p) if p
                      else queries[q].reference(raw))
                  for k, (q, p) in instances.items()}
    by_key = {k: queries[q] for k, (q, _) in instances.items()}
    if probe is not None:
        probe.update(raw=raw, references=references, instances=instances,
                     queries=queries)
    verdict = compare.judge([(r["key"], r["table"]) for r in requests
                             if r["ok"]], references, by_key)
    stages["check_s"] = time.perf_counter() - t
    failed = sum(1 for r in requests if not r["ok"]) + never
    attempted = len(requests) + never
    compared = [
        ("answers_wrong", verdict["answers_wrong"], 0),
        ("decimal_units_off_max", verdict["decimal_units_off_max"], 0),
        ("answers_never_came", failed, 0),
        ("left_the_chip", sum(hidden.values()), 0),
        ("answers_compared", attempted - failed, None),
    ]
    correct = (attempted > failed and all(
        lim is None or val <= lim for _, val, lim in compared))
    for why in verdict["whys"] + [r["error"] for r in requests
                                  if r["error"]][:3]:
        log(f"[bench] wrong: {why}")
    if hidden:
        log(f"[bench] the device was hidden: {hidden}")

    # -- metrics -----------------------------------------------------------
    ok = [r for r in requests if r["ok"]]
    window_s = end - start
    ctx = {
        "cell": cell, "config": config, "mix": mix, "device": device,
        "peaks": peaks, "rows": rows, "queries": queries, "requests": ok,
        "start": start, "end": end, "window_s": window_s,
        "first_query_s": first, "stages": stages, "spans": spans,
        "programs_in_window": in_window, "trace": None,
        "width": datagen.DEVICE_WIDTH, "percentile": percentile,
        "latency_s": [r["t1"] - r["t0"] for r in ok],
        "request_spans": spanred.by_request(spans, ok),
    }
    ctx["setup_s"] = setup_s
    ctx["answers_wrong"] = verdict["answers_wrong"]
    e2e = {m["name"]: load_by_path("end_to_end", m["name"]).read(ctx)
           for m in spec["end_to_end"]}
    result_device = dict(device, memory_peak_bytes=int(memory_peak))
    metrics, breakdown = {}, None
    if trace:
        try:
            reduced = _reduce_trace(trace_info, spans, ok, log, keep_trace)
        except ValueError as e:
            if require_chip:  # a traced run with no device operation fails
                raise
            log(f"[bench] rehearsal: no device timeline ({e})")
            reduced = None
        if reduced:
            ctx["trace"] = reduced
            result_device["busy_s"] = reduced["busy_s"]
            result_device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        for m in spec["per_layer"]:
            value = load_by_path("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    notes = {
        "stages_s": stages, "compile": meter.read(),
        "compile_after_warm_up": warm_meter, "program_counters": counters,
        "programs_in_window": in_window, "requests_in_window": len(ok),
        "window_s": window_s, "rows": rows,
        "end_to_end_seen": {k: v for k, v in e2e.items() if v is not None},
        "latency_ms": ({f"p{int(q * 1000) / 10:g}": percentile(
            ctx["latency_s"], q) * 1e3 for q in
            (0.5, 0.75, 0.9, 0.95, 0.98, 0.99, 0.995, 1.0)} if ok else None),
        "bytes_in_use": mem[0].get("bytes_in_use"),
    }
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": result_device}
    if breakdown:
        out["breakdown"] = breakdown
    out["notes"] = notes
    out["compared"] = {name: {"value": val, "limit": lim}
                       for name, val, lim in compared}
    for name, val, lim in compared:
        log(f"[bench] compared {name} = {val!r} limit {lim!r}")
    return out


def _trace_window(mix, seconds, start, traced, log) -> dict:
    """Runs the profiler over ``trace_seconds`` of the window, bracketed by
    the ``bench:window`` annotation; returns the window on the host's clock
    (which is the program's spans' clock too)."""
    import jax
    lead = min(1.0, seconds * 0.1)
    length = min(float(mix["trace_seconds"]), max(0.2, seconds - 2 * lead))
    time.sleep(max(0.0, start + lead - time.perf_counter()))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the host path
    options.host_tracer_level = 1  # annotations, not every runtime call
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    info = {}
    try:
        with jax.profiler.TraceAnnotation(tracered.WINDOW):
            info["enter_ns"] = time.perf_counter_ns()
            traced["on"] = True
            time.sleep(length)
            traced["on"] = False
            info["exit_ns"] = time.perf_counter_ns()
    finally:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"[bench] traced {length:.1f}s; stop_trace took "
            f"{time.perf_counter() - t:.1f}s")
    return info


def _reduce_trace(info: dict, spans: list, requests: list, log,
                  keep=None) -> dict:
    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb, found {files}")
    t = time.perf_counter()
    planes = tracered.read_planes(files[0])
    lo = [h for h in planes["host"] if h[0] == tracered.WINDOW][0][1]
    shift = lo - info["enter_ns"]  # host perf_counter_ns -> trace clock
    aligned = [(e["name"], e["start_ns"] + shift,
                e["start_ns"] + e["dur_ns"] + shift) for e in spans]
    out = tracered.reduce_planes(planes, aligned)
    # each request counts by the share of its wall that lies in the window
    w0, w1 = info["enter_ns"] / 1e9, info["exit_ns"] / 1e9
    out["fractions"] = [
        (r["query"], (min(r["t1"], w1) - max(r["t0"], w0))
         / max(r["t1"] - r["t0"], 1e-9))
        for r in requests if r["t1"] > w0 and r["t0"] < w1]
    out["queries"] = sum(f for _, f in out["fractions"])
    log(f"[bench] trace {os.path.getsize(files[0])} bytes reduced in "
        f"{time.perf_counter() - t:.1f}s: busy {out['busy_s']:.4f}s of "
        f"{out['window_s']:.4f}s, {out['launches']} launches, "
        f"{out['queries']:.2f} queries")
    if keep:  # --keep-trace: how testdata/recorded.xplane.pb was made
        os.makedirs(keep, exist_ok=True)
        shutil.copy(files[0], os.path.join(keep, "recorded.xplane.pb"))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return out
