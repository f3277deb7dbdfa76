#!/usr/bin/env python
"""Serving load generator: dynamic batching vs the serial Predictor.

Measures what the serving layer is *for*: request throughput and tail
latency under concurrency.  Four scenarios over the same model:

- **serial** — one thread calling ``Predictor.forward`` per request: the
  baseline an embedder gets without the serving layer.
- **closed** — N closed-loop clients issuing back-to-back requests into a
  :class:`ModelServer` (each client waits for its response before sending
  the next): measures coalescing gain at saturation (and doubles as the
  capacity estimate the sweep scales from).
- **open** — Poisson arrivals at a target rate submitted asynchronously:
  measures tail latency and rejection behaviour at a fixed offered load
  (closed-loop self-throttles and can't show overload).
- **sweep** — open-loop Poisson points at multiples of measured capacity,
  up to >10x, with a mixed SLO-class workload (realtime with a deadline,
  standard, batch): the saturation curve (offered vs achieved QPS) plus
  per-class p50/p99 and shed rate at every point.  The story it must
  tell: past saturation the scheduler sheds ``batch``/``standard`` with
  429s while realtime latency stays bounded — overload degrades the
  cheap traffic, not the tail.

Reports p50/p90/p99/mean end-to-end latency (ms), throughput (req/s and
rows/s), realized mean batch size, padding overhead, and the compiled
program count (``op_jit_cache_misses_total`` for ``Executor::Forward``) —
one JSON document on stdout (or ``--out``).  ``--history-out`` also
writes the canonical sentinel round (``serving_p99_ms_realtime``,
``serving_shed_rate_overload``, ...) for ``bench_history/``.

Run:  python tools/bench_serving.py [--smoke] [--out results.json]
      python tools/bench_serving.py --smoke \\
          --history-out bench_history/serving_r14.canonical.json
"""
import argparse
import json
import os
import queue
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd, telemetry  # noqa: E402
from mxnet_tpu.predictor import Predictor  # noqa: E402
from mxnet_tpu.serving import (AdmissionError, ModelServer,  # noqa: E402
                               QueueFullError, ServingError)

S = mx.symbol


def build_model(in_dim, hidden, classes):
    """data (n, in_dim) -> FC(hidden) relu x2 -> FC(classes) softmax."""
    x = S.var("data")
    h = S.Activation(S.FullyConnected(x, num_hidden=hidden, name="fc1"),
                     act_type="relu")
    h = S.Activation(S.FullyConnected(h, num_hidden=hidden, name="fc2"),
                     act_type="relu")
    out = S.softmax(S.FullyConnected(h, num_hidden=classes, name="fc3"),
                    axis=1, name="prob")
    rng = np.random.RandomState(0)
    shapes, _, _ = out.infer_shape(data=(1, in_dim))
    params = {n: nd.array(rng.uniform(-0.1, 0.1, s).astype(np.float32))
              for n, s in zip(out.list_arguments(), shapes) if n != "data"}
    return out, params


def percentiles(lat_s):
    if not lat_s:
        return {}
    a = np.asarray(lat_s) * 1e3
    return {"p50_ms": float(np.percentile(a, 50)),
            "p90_ms": float(np.percentile(a, 90)),
            "p99_ms": float(np.percentile(a, 99)),
            "mean_ms": float(a.mean()),
            "max_ms": float(a.max())}


def bench_serial(sym, params, in_dim, requests):
    """One request at a time through a batch-1 Predictor."""
    pred = Predictor(sym.tojson(), params, input_shapes={"data": (1, in_dim)})
    rng = np.random.RandomState(1)
    X = rng.uniform(-1, 1, (requests, 1, in_dim)).astype(np.float32)
    pred.forward(data=X[0])[0].asnumpy()          # compile outside timing
    lat = []
    t0 = time.perf_counter()
    for i in range(requests):
        t = time.perf_counter()
        pred.forward(data=X[i])[0].asnumpy()
        lat.append(time.perf_counter() - t)
    wall = time.perf_counter() - t0
    return {"requests": requests, "wall_s": round(wall, 4),
            "throughput_rps": round(requests / wall, 1), **percentiles(lat)}


def _serving_counters():
    def misses():
        return telemetry.value("op_jit_cache_misses_total",
                               op="Executor::Forward")
    batch_hist = telemetry.registry().get("serving_batch_rows")
    pad = lambda: telemetry.value("serving_padding_rows_total")  # noqa: E731
    return misses, batch_hist, pad


def bench_closed(server, in_dim, clients, requests_per_client):
    """Closed loop: each client waits for its response before the next."""
    misses, batch_hist, pad = _serving_counters()
    h0, m0, p0 = batch_hist.get(), misses(), pad()
    rng = np.random.RandomState(2)
    X = rng.uniform(-1, 1, (clients, in_dim)).astype(np.float32)
    lat, errors, lock = [], [], threading.Lock()

    def client(i):
        mine = []
        for _ in range(requests_per_client):
            t = time.perf_counter()
            try:
                server.predict({"data": X[i]}, timeout=120.0)
            except ServingError as e:
                with lock:
                    errors.append(repr(e))
                return
            mine.append(time.perf_counter() - t)
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    h1 = batch_hist.get()
    batches = h1["count"] - h0["count"]
    rows = h1["sum"] - h0["sum"]
    total = clients * requests_per_client
    return {"clients": clients, "requests": total,
            "errors": len(errors), "wall_s": round(wall, 4),
            "throughput_rps": round(total / wall, 1),
            "batches": int(batches),
            "mean_batch_rows": round(rows / max(batches, 1), 2),
            "padding_rows": int(p0 is not None and pad() - p0),
            "new_compiles": misses() - m0, **percentiles(lat)}


def bench_open(server, in_dim, rate_rps, duration_s, deadline_ms):
    """Open loop: Poisson arrivals at ``rate_rps`` regardless of
    completions; waits happen on collector threads so arrivals never
    self-throttle."""
    misses, batch_hist, pad = _serving_counters()
    h0, p0 = batch_hist.get(), pad()
    rng = np.random.RandomState(3)
    X = rng.uniform(-1, 1, (64, in_dim)).astype(np.float32)
    lat, lock = [], threading.Lock()
    outcomes = {"ok": 0, "rejected": 0, "deadline": 0, "error": 0}
    pending = []

    def collect(req, t_submit):
        try:
            req.result(120.0)
            with lock:
                outcomes["ok"] += 1
                lat.append(time.perf_counter() - t_submit)
        except ServingError:
            with lock:
                outcomes[req.outcome if req.outcome in outcomes
                         else "error"] += 1

    t0 = time.perf_counter()
    end = t0 + duration_s
    n = 0
    next_t = t0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.001))
            continue
        next_t += rng.exponential(1.0 / rate_rps)
        t_submit = time.perf_counter()
        try:
            req = server.submit({"data": X[n % len(X)]},
                                deadline_ms=deadline_ms or None)
        except ServingError as e:
            with lock:
                outcomes["rejected" if "queue full" in str(e)
                         else "error"] += 1
            continue
        finally:
            n += 1
        t = threading.Thread(target=collect, args=(req, t_submit))
        t.start()
        pending.append(t)
    for t in pending:
        t.join(120.0)
    wall = time.perf_counter() - t0
    h1 = batch_hist.get()
    batches = h1["count"] - h0["count"]
    rows = h1["sum"] - h0["sum"]
    return {"offered_rps": rate_rps, "duration_s": duration_s,
            "submitted": n, "outcomes": dict(outcomes),
            "achieved_rps": round(outcomes["ok"] / wall, 1),
            "batches": int(batches),
            "mean_batch_rows": round(rows / max(batches, 1), 2),
            "padding_rows": int(pad() - p0), **percentiles(lat)}


#: SLO-class workload mix for the saturation sweep: (class, share of
#: arrivals, carries the realtime deadline?).  30/40/30 is the classic
#: "interactive + default + offline backfill" blend.
CLASS_MIX = (("realtime", 0.30, True),
             ("standard", 0.40, False),
             ("batch", 0.30, False))


def bench_open_slo(server, in_dim, rate_rps, duration_s, rt_deadline_ms,
                   collectors_per_class=8):
    """One open-loop Poisson point with the CLASS_MIX workload.

    Arrivals never self-throttle (submission is non-blocking; waiting
    happens on small collector pools — at most queue_depth + one batch
    of requests are ever in flight, so the pools keep up and a thread
    per request at 12x capacity is avoided).  One pool **per SLO class**:
    the scheduler executes classes out of submission order, so a shared
    pool would head-of-line block on a deprioritized batch request while
    completed realtime responses queue behind it, inflating the measured
    realtime tail.  Within one class completion order tracks submission
    order (EDF with a uniform deadline offset == FIFO), so per-class
    pools measure true latency.  Returns offered/achieved QPS,
    shed/reject rates, and per-class outcome counts + p50/p99.
    """
    rng = np.random.RandomState(int(rate_rps) % 7919 + 5)
    X = rng.uniform(-1, 1, (64, in_dim)).astype(np.float32)
    classes = [c for c, _, _ in CLASS_MIX]
    shares = np.asarray([s for _, s, _ in CLASS_MIX])
    shares = shares / shares.sum()
    rt_deadline = {c: (rt_deadline_ms if dl else None)
                   for c, _, dl in CLASS_MIX}
    lock = threading.Lock()
    lat = {c: [] for c in classes}
    outcomes = {c: {"ok": 0, "shed": 0, "rejected": 0, "deadline": 0,
                    "error": 0} for c in classes}
    done_q = {c: queue.Queue() for c in classes}

    def collect(q):
        while True:
            item = q.get()
            if item is None:
                return
            req, t_submit, cls = item
            try:
                req.result(120.0)
                dt = time.perf_counter() - t_submit
                with lock:
                    outcomes[cls]["ok"] += 1
                    lat[cls].append(dt)
            except ServingError:
                out = req.outcome if req.outcome in outcomes[cls] \
                    else "error"
                with lock:
                    outcomes[cls][out] += 1

    pool = [threading.Thread(target=collect, args=(done_q[c],), daemon=True)
            for c in classes for _ in range(collectors_per_class)]
    for t in pool:
        t.start()
    t0 = time.perf_counter()
    end = t0 + duration_s
    n = 0
    next_t = t0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.001))
            continue
        next_t += rng.exponential(1.0 / rate_rps)
        cls = classes[int(rng.choice(len(classes), p=shares))]
        t_submit = time.perf_counter()
        try:
            req = server.submit({"data": X[n % len(X)]},
                                deadline_ms=rt_deadline[cls],
                                slo_class=cls)
        except AdmissionError:
            with lock:
                outcomes[cls]["shed"] += 1
            continue
        except QueueFullError:
            with lock:
                outcomes[cls]["rejected"] += 1
            continue
        except ServingError:
            with lock:
                outcomes[cls]["error"] += 1
            continue
        finally:
            n += 1
        done_q[cls].put((req, t_submit, cls))
    for c in classes:
        for _ in range(collectors_per_class):
            done_q[c].put(None)
    for t in pool:
        t.join(120.0)
    wall = time.perf_counter() - t0
    ok = sum(o["ok"] for o in outcomes.values())
    shed = sum(o["shed"] for o in outcomes.values())
    rejected = sum(o["rejected"] for o in outcomes.values())
    per_class = {}
    for c in classes:
        per_class[c] = {"outcomes": dict(outcomes[c]), **percentiles(lat[c])}
    return {"offered_rps": round(rate_rps, 1), "duration_s": duration_s,
            "submitted": n,
            "achieved_rps": round(ok / wall, 1),
            "shed_rate": round(shed / max(n, 1), 4),
            "reject_rate": round(rejected / max(n, 1), 4),
            "classes": per_class}


def bench_sweep(server, in_dim, capacity_rps, multiples, point_duration_s,
                rt_deadline_ms):
    """The saturation curve: one open-loop SLO point per capacity
    multiple (the last well past 10x), worst-case offered load last so
    earlier points aren't polluted by a saturated queue."""
    points = []
    for mult in multiples:
        rate = max(capacity_rps * mult, 1.0)
        pt = bench_open_slo(server, in_dim, rate, point_duration_s,
                            rt_deadline_ms)
        pt["capacity_multiple"] = mult
        points.append(pt)
        # let the queue fully drain between points: each point measures
        # its own offered load, not the previous point's backlog
        while len(server._batcher):
            time.sleep(0.01)
    return points


def canonical_round(doc, round_name, source):
    """The sentinel-canonical round document for ``bench_history/``."""
    sat = doc["sweep"][-1]
    rt = sat["classes"]["realtime"]
    metrics = {}
    if rt.get("p99_ms") is not None:
        metrics["serving_p99_ms_realtime"] = round(rt["p99_ms"], 2)
    metrics["serving_shed_rate_overload"] = sat["shed_rate"]
    metrics["serving_throughput_rps"] = doc["closed"]["throughput_rps"]
    if doc.get("warmup_seconds") is not None:
        metrics["serving_warmup_seconds"] = round(doc["warmup_seconds"], 3)
    metrics["post_warmup_compiles"] = doc.get("post_warmup_compiles", 0)
    return {
        "round": round_name,
        "source": source,
        "kind": "serving_gateway",
        "metrics": metrics,
        "context": {
            "platform": "cpu",
            "capacity_rps": doc["closed"]["throughput_rps"],
            "overload_offered_rps": sat["offered_rps"],
            "overload_achieved_rps": sat["achieved_rps"],
            "capacity_multiple": sat["capacity_multiple"],
            "class_mix": {c: s for c, s, _ in CLASS_MIX},
            "rt_deadline_ms": doc["config"].get("rt_deadline_ms"),
            "note": "realtime p99 + shed rate at the >10x-capacity "
                    "open-loop point; shedding (429) is the designed "
                    "overload response — shed_rate collapsing to 0 "
                    "under 12x load means admission control broke",
        },
    }


def main():
    from mxnet_tpu import program_cache
    program_cache.place()       # the one decision on where compiles persist
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--in-dim", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--timeout-ms", type=float, default=2.0,
                    help="batch window (MXNET_SERVING_BATCH_TIMEOUT_MS)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=200,
                    help="serial total; also per-client closed-loop count")
    ap.add_argument("--rate", type=float, default=300.0,
                    help="open-loop offered load (req/s); 0 skips open loop")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop duration (s)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="open-loop per-request deadline (0 = none)")
    ap.add_argument("--queue-depth", type=int, default=512)
    ap.add_argument("--sweep-multiples", default="0.5,1,2,5,10,12",
                    help="capacity multiples for the saturation sweep "
                         "('' skips the sweep)")
    ap.add_argument("--sweep-duration", type=float, default=4.0,
                    help="open-loop duration per sweep point (s)")
    ap.add_argument("--rt-deadline-ms", type=float, default=200.0,
                    help="realtime-class deadline in the sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny counts: CI-sized sanity run")
    ap.add_argument("--out", help="write the JSON document here too")
    ap.add_argument("--history-out",
                    help="write the canonical sentinel round here "
                         "(e.g. bench_history/serving_r14.canonical.json)")
    ap.add_argument("--round", default="r14",
                    help="round name stamped on --history-out")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.clients = 20, 4
        args.rate, args.duration = 100.0, 1.0
        args.sweep_multiples = "1,12"
        args.sweep_duration = 1.0

    telemetry.enable()
    sym, params = build_model(args.in_dim, args.hidden, args.classes)

    doc = {"bench": "serving",
           "model": {"in_dim": args.in_dim, "hidden": args.hidden,
                     "classes": args.classes},
           "config": {"max_batch": args.max_batch,
                      "batch_timeout_ms": args.timeout_ms,
                      "clients": args.clients,
                      "queue_depth": args.queue_depth,
                      "rt_deadline_ms": args.rt_deadline_ms}}

    doc["serial"] = bench_serial(sym, params, args.in_dim, args.requests)

    server = ModelServer(sym.tojson(), params,
                         example_shapes={"data": (args.in_dim,)},
                         max_batch_size=args.max_batch,
                         batch_timeout_ms=args.timeout_ms,
                         queue_depth=args.queue_depth)
    m0 = telemetry.value("op_jit_cache_misses_total", op="Executor::Forward")
    server.start()
    doc["warmup_compiles"] = telemetry.value(
        "op_jit_cache_misses_total", op="Executor::Forward") - m0
    doc["warmup_seconds"] = server.warmup_seconds
    doc["buckets"] = list(server.config.batch_buckets)
    try:
        doc["closed"] = bench_closed(server, args.in_dim, args.clients,
                                     args.requests)
        if args.rate > 0:
            doc["open"] = bench_open(server, args.in_dim, args.rate,
                                     args.duration, args.deadline_ms)
        multiples = [float(m) for m in args.sweep_multiples.split(",")
                     if m.strip()]
        if multiples:
            capacity = max(doc["closed"]["throughput_rps"], 1.0)
            doc["sweep"] = bench_sweep(server, args.in_dim, capacity,
                                       multiples, args.sweep_duration,
                                       args.rt_deadline_ms)
        doc["post_warmup_compiles"] = telemetry.value(
            "op_jit_cache_misses_total",
            op="Executor::Forward") - m0 - doc["warmup_compiles"]
    finally:
        server.stop()

    if doc["serial"].get("throughput_rps") and \
            doc["closed"].get("throughput_rps"):
        doc["closed_vs_serial_speedup"] = round(
            doc["closed"]["throughput_rps"]
            / doc["serial"]["throughput_rps"], 2)

    text = json.dumps(doc, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.history_out:
        if "sweep" not in doc:
            raise SystemExit("--history-out needs the sweep "
                             "(--sweep-multiples was empty)")
        rnd = canonical_round(doc, args.round,
                              "tools/bench_serving.py --smoke" if args.smoke
                              else "tools/bench_serving.py")
        with open(args.history_out, "w") as f:
            json.dump(rnd, f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
