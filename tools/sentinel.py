#!/usr/bin/env python
"""Perf regression sentinel: canonical bench trajectory + tolerance gate.

The repo's perf record is heterogeneous — ``BENCH_rNN.json`` driver
wrappers, ``MULTICHIP_rNN.json`` mesh rounds, and (since the run ledger)
``bench_result`` events in ``runlog`` JSONL files — and it was compared
by hand, if at all.  This tool is the mechanical comparison, in the
MLPerf round-over-round mold:

1. **normalize**: every input shape collapses into one canonical round
   document ``{"round", "source", "kind", "metrics": {name: value},
   "context": {...}}`` with stable metric names (resnet50_img_per_sec,
   lstm_tokens_per_sec, multichip_scaling_efficiency, ...).
2. **compare**: candidate vs committed baseline, one tolerance band per
   metric (direction + relative tolerance + absolute slack — spread and
   overhead metrics get absolute points, throughput gets percent).
   Improvements always pass; regressions beyond the band FAIL, beyond
   half the band WARN.  Output is a ranked markdown verdict table
   (worst first) or JSON; exit is nonzero on any FAIL.
3. **--update-baseline**: promote the candidate to
   ``bench_history/baseline.json`` after a reviewed run.

``bench.py`` appends each round to the run ledger and invokes
:func:`compare` automatically (``BENCH_SENTINEL=0`` to opt out), so a
regression is caught the moment the bench runs — not at the next human
re-read of the trajectory.

Stdlib-only on purpose: the gate must run anywhere (CI shard, dev box,
pre-push hook) without importing the framework or jax.

    python tools/sentinel.py --candidate BENCH_r05.json
    python tools/sentinel.py --candidate runs/ledger.jsonl --format md
    python tools/sentinel.py --normalize BENCH_r0*.json -o bench_history/
    python tools/sentinel.py --candidate new.json --update-baseline
    python tools/sentinel.py --smoke
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "bench_history", "baseline.json")

# ---------------------------------------------------------------------------
# tolerance bands: metric -> (direction, rel_tol, abs_slack)
#
# direction says which way is GOOD; a move the good way always passes.
# The band the bad way is max(rel_tol * |baseline|, abs_slack): percent
# for throughput-like metrics, absolute points for spreads/overheads
# (2% -> 5% spread is a real regression a relative band would miss when
# the baseline is small, and a 50% relative band would miss when it is
# large).  A candidate breaching the full band FAILs, half of it WARNs.
# ---------------------------------------------------------------------------
TOLERANCES: Dict[str, Tuple[str, float, float]] = {
    "resnet50_img_per_sec":         ("higher", 0.10, 0.0),
    "resnet50_mfu_pct":             ("higher", 0.10, 0.0),
    "resnet50_step_spread_pct":     ("lower",  0.00, 3.0),
    "lstm_tokens_per_sec":          ("higher", 0.10, 0.0),
    "lstm_mfu_pct":                 ("higher", 0.10, 0.0),
    "lstm_step_spread_pct":         ("lower",  0.00, 3.0),
    "multichip_img_per_sec":        ("higher", 0.10, 0.0),
    "multichip_scaling_efficiency": ("higher", 0.15, 0.0),
    "serving_p99_ms":               ("lower",  0.20, 0.0),
    "serving_throughput_rps":       ("higher", 0.10, 0.0),
    # SLO gateway (ISSUE 14): realtime tail at the >10x-capacity
    # open-loop point.  Absolute slack because the CPU box's batch
    # timing wobbles tens of ms run to run; a realtime tail that grows
    # past band means admission control stopped protecting the class.
    "serving_p99_ms_realtime":      ("lower",  0.30, 25.0),
    # shed rate at 12x offered load: HIGHER is healthy (overload is
    # absorbed as explicit 429s).  A collapse toward 0 under the same
    # overload means shedding broke and the tail is eating it.
    "serving_shed_rate_overload":   ("higher", 0.00, 0.25),
    "post_warmup_compiles":         ("lower",  0.00, 0.0),
    "atlas_coverage_pct":           ("higher", 0.00, 5.0),
    "monitor_overhead_pct":         ("lower",  0.00, 1.0),
    "sampler_overhead_pct":         ("lower",  0.00, 1.0),
    # donation-safe async checkpoints (ISSUE 13): amortized per-step cost
    # of the live TrainCheckpointer; the acceptance bar is <3%
    "checkpoint_overhead_pct":      ("lower",  0.00, 3.0),
    # cold-start currency (program_cache.py).  Lower is better; a warm
    # deploy (prefilled cache dir) improves 5x+ and always passes.  The
    # bands are generous because the COLD path is compile-time noise on
    # shared CPU — only a 1.5x-plus-slack blowup is a real regression
    # (an accidental cache bypass shows up as exactly that).
    "step_first_compile_seconds":   ("lower",  0.50, 3.0),
    "serving_warmup_seconds":       ("lower",  0.50, 2.0),
    # device-memory observability (ISSUE 16): the resnet50 round's
    # per-device peak — LOWER is good; a step that suddenly holds more
    # HBM regressed even if it got faster.  Generous absolute slack
    # because the CPU census-fallback peak moves with unrelated process
    # residents.
    "resnet50_peak_bytes_in_use":   ("lower",  0.25, float(8 << 20)),
    # census + ledger hooks must stay at noise level, same bar as the
    # monitor/sampler
    "memwatch_overhead_pct":        ("lower",  0.00, 1.0),
    # bf16 mixed precision (ISSUE 19).  Throughput on CPU is an
    # emulation canary (XLA upcasts per op) that wobbles ±50% with host
    # load at the small CPU iteration count, so the band only catches a
    # collapse; the load-bearing rows are the footprint ratios — params
    # must stay at ~half of fp32 and the peak must not creep back
    # toward the fp32 peak.  Re-band on a real chip.
    "resnet50_bf16_img_per_sec":    ("higher", 0.50, 0.0),
    "resnet50_bf16_peak_bytes_in_use": ("lower", 0.25, float(8 << 20)),
    # ratios are bounded [0, ~1]: absolute slack, no relative band
    "bf16_params_ratio":            ("lower",  0.00, 0.05),
    "bf16_params_activations_ratio": ("lower", 0.00, 0.08),
    # transformer LM workload (ISSUE 20).  CPU throughput on the small
    # iteration count wobbles with host load (same story as bf16), so
    # the bands catch a collapse, not a wobble; re-band on a real chip.
    # The zero-tolerance compile row and the atlas floor are the
    # load-bearing gates — they are also what --smoke asserts.
    "transformer_tokens_per_sec":   ("higher", 0.35, 0.0),
    "transformer_mfu_pct":          ("higher", 0.35, 0.0),
    "transformer_step_spread_pct":  ("lower",  0.00, 8.0),
    "transformer_post_warmup_compiles": ("lower", 0.00, 0.0),
    "transformer_atlas_coverage_pct": ("higher", 0.00, 5.0),
    "transformer_peak_bytes_in_use": ("lower", 0.30, float(8 << 20)),
}
#: band for metrics not in the table: 15% relative, either direction bad
#: is unknowable, so assume higher-is-better (throughput-style default).
DEFAULT_BAND = ("higher", 0.15, 0.0)


def _num(v) -> Optional[float]:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if f == f and abs(f) != float("inf") else None


# ---------------------------------------------------------------------------
# normalizers: every known perf-record shape -> canonical round doc
# ---------------------------------------------------------------------------
def _round_of(path: str) -> Optional[str]:
    m = re.search(r"r(\d+)", os.path.basename(path or ""))
    return "r%02d" % int(m.group(1)) if m else None


def _norm_bench_parsed(parsed: dict, source: str) -> dict:
    """The ``parsed`` block of a BENCH_rNN wrapper / bench.py stdout."""
    metrics: Dict[str, float] = {}
    ctx: Dict[str, object] = {}

    def put(name, v):
        v = _num(v)
        if v is not None:
            metrics[name] = v

    put("resnet50_img_per_sec", parsed.get("value"))
    put("resnet50_mfu_pct", parsed.get("mfu_pct"))
    put("resnet50_step_spread_pct", parsed.get("step_spread_pct"))
    put("step_first_compile_seconds",
        parsed.get("step_first_compile_seconds"))
    put("checkpoint_overhead_pct", parsed.get("checkpoint_overhead_pct"))
    lstm = parsed.get("lstm")
    if isinstance(lstm, dict) and "error" not in lstm:
        put("lstm_tokens_per_sec", lstm.get("value"))
        put("lstm_mfu_pct", lstm.get("mfu_pct"))
        put("lstm_step_spread_pct", lstm.get("step_spread_pct"))
    health = parsed.get("health")
    if isinstance(health, dict):
        put("monitor_overhead_pct", health.get("monitor_overhead_pct"))
        put("sampler_overhead_pct", health.get("sampler_overhead_pct"))
    memory = parsed.get("memory")
    if isinstance(memory, dict) and "error" not in memory:
        put("resnet50_peak_bytes_in_use", memory.get("peak_bytes_in_use"))
        put("memwatch_overhead_pct", memory.get("memwatch_overhead_pct"))
    atlas = parsed.get("atlas")
    if isinstance(atlas, dict) and "error" not in atlas:
        covs = [_num(a.get("coverage_pct")) for a in atlas.values()
                if isinstance(a, dict)]
        covs = [c for c in covs if c is not None]
        if covs:
            # the gate watches the WORST program: attribution rotting in
            # one program is invisible to a mean over many healthy ones
            metrics["atlas_coverage_pct"] = min(covs)
    for k in ("window_suspect", "dtype", "batch", "unit"):
        if k in parsed:
            ctx[k] = parsed[k]
    # r01-style records predate the window validation: no scaling ratio
    # means the number never proved itself — flagged, never baselined
    if "window_scaling_ratio" not in parsed:
        ctx["unvalidated"] = True
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "bench", "metrics": metrics, "context": ctx}


def _norm_bench_bf16(doc: dict, source: str) -> dict:
    """bench.py --bf16 record (ISSUE 19).  The throughput row keeps the
    model-qualified metric name the bench emitted (``resnet50_bf16_*``);
    the footprint ratios are model-agnostic bands — on any model, bf16
    params at more than ~half of fp32 means the cast policy broke."""
    metrics: Dict[str, float] = {}

    def put(name, v):
        v = _num(v)
        if v is not None:
            metrics[name] = v

    name = str(doc.get("metric") or "bf16_img_per_sec")
    put(name, doc.get("value"))
    put(name.replace("_img_per_sec", "_peak_bytes_in_use"),
        doc.get("peak_bytes_in_use"))
    put("bf16_params_ratio", doc.get("params_ratio"))
    put("bf16_params_activations_ratio",
        doc.get("params_activations_ratio"))
    ctx = {k: doc[k] for k in ("model", "batch", "platform", "unit",
                               "throughput_chip_pending", "loss_delta",
                               "matched_convergence", "footprint_halved",
                               "ok") if k in doc}
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "bench_bf16", "metrics": metrics, "context": ctx}


def _norm_bench_transformer(doc: dict, source: str) -> dict:
    """bench.py --transformer record (ISSUE 20): decoder-LM tokens/s +
    MFU, the zero-tolerance post-warmup compile count, the worst-program
    atlas coverage and the per-device peak.  Metric names are
    transformer-qualified so merging into the baseline never collides
    with the resnet/serving rows of the same name."""
    metrics: Dict[str, float] = {}

    def put(name, v):
        v = _num(v)
        if v is not None:
            metrics[name] = v

    put("transformer_tokens_per_sec", doc.get("value"))
    put("transformer_mfu_pct", doc.get("mfu_pct"))
    put("transformer_step_spread_pct", doc.get("step_spread_pct"))
    put("transformer_post_warmup_compiles",
        doc.get("post_warmup_compiles"))
    put("transformer_atlas_coverage_pct",
        doc.get("atlas_coverage_min_pct"))
    put("transformer_peak_bytes_in_use", doc.get("peak_bytes_in_use"))
    ctx = {k: doc[k] for k in ("config", "batch", "seq_len", "dtype",
                               "platform", "n_params", "unit",
                               "attention_dispatch", "window_suspect",
                               "last_loss", "ok") if k in doc}
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "bench_transformer", "metrics": metrics,
            "context": ctx}


def _norm_multichip(doc: dict, source: str) -> dict:
    metrics: Dict[str, float] = {}
    v = _num(doc.get("value") if doc.get("value") is not None
             else doc.get("img_per_sec"))
    if v is not None:
        metrics["multichip_img_per_sec"] = v
    e = _num(doc.get("scaling_efficiency"))
    if e is not None:
        metrics["multichip_scaling_efficiency"] = e
    ctx = {k: doc[k] for k in ("platform", "n_devices", "model", "batch",
                               "window_suspect", "ok", "skipped")
           if k in doc}
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "multichip", "metrics": metrics, "context": ctx}


def _norm_serving(doc: dict, source: str) -> dict:
    """tools/bench_serving.py result or a ledger serving payload."""
    metrics: Dict[str, float] = {}
    for src, dst in (("p99_ms", "serving_p99_ms"),
                     ("latency_p99_ms", "serving_p99_ms"),
                     ("throughput_rps", "serving_throughput_rps"),
                     ("post_warmup_compiles", "post_warmup_compiles"),
                     ("warmup_seconds", "serving_warmup_seconds")):
        v = _num(doc.get(src))
        if v is not None and dst not in metrics:
            metrics[dst] = v
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "serving", "metrics": metrics, "context": {}}


def _norm_serving_gateway(doc: dict, source: str) -> dict:
    """tools/bench_serving.py output with the SLO saturation sweep: the
    gated metrics come from the worst (last) sweep point."""
    metrics: Dict[str, float] = {}
    ctx: Dict[str, object] = {}
    closed = doc.get("closed") or {}
    v = _num(closed.get("throughput_rps"))
    if v is not None:
        metrics["serving_throughput_rps"] = v
    v = _num(doc.get("warmup_seconds"))
    if v is not None:
        metrics["serving_warmup_seconds"] = v
    v = _num(doc.get("post_warmup_compiles"))
    if v is not None:
        metrics["post_warmup_compiles"] = v
    sweep = doc.get("sweep") or []
    if sweep:
        sat = sweep[-1]
        v = _num(sat.get("shed_rate"))
        if v is not None:
            metrics["serving_shed_rate_overload"] = v
        rt = (sat.get("classes") or {}).get("realtime") or {}
        v = _num(rt.get("p99_ms"))
        if v is not None:
            metrics["serving_p99_ms_realtime"] = v
        ctx["overload_offered_rps"] = sat.get("offered_rps")
        ctx["capacity_multiple"] = sat.get("capacity_multiple")
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "serving_gateway", "metrics": metrics, "context": ctx}


def _norm_ledger(path: str) -> dict:
    """A runlog JSONL: fold every bench_result / healthz event into one
    candidate round (the run's final state wins per metric)."""
    metrics: Dict[str, float] = {}
    ctx: Dict[str, object] = {}
    run_id = None
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail line: JSONL readers skip, not die
            if not isinstance(rec, dict):
                continue
            run_id = rec.get("run_id", run_id)
            ev = rec.get("event")
            if ev == "bench_result":
                res = rec.get("result")
                if isinstance(res, dict):
                    sub = normalize(res, rec.get("source_name", path))
                    metrics.update(sub["metrics"])
                    ctx.update(sub["context"])
            elif ev == "healthz":
                v = _num(rec.get("post_warmup_compiles"))
                if v is not None:
                    metrics["post_warmup_compiles"] = v
            elif ev == "serving_warmup":
                v = _num(rec.get("seconds"))
                if v is not None:
                    metrics["serving_warmup_seconds"] = v
            elif ev == "run_start":
                env = rec.get("env")
                if isinstance(env, dict):
                    ctx.setdefault("step_env", {
                        k: env[k] for k in ("MXNET_TPU_FUSED_STEP",)
                        if k in env})
    if run_id:
        ctx["run_id"] = run_id
    return {"round": _round_of(path), "source": os.path.basename(path),
            "kind": "ledger", "metrics": metrics, "context": ctx}


def normalize(doc, source: str = "<inline>") -> dict:
    """Dispatch on shape: canonical round / driver wrapper / bench parsed
    / multichip / serving dicts all collapse to the canonical form."""
    if isinstance(doc, str):
        if doc.endswith(".jsonl"):
            return _norm_ledger(doc)
        with open(doc, "r", encoding="utf-8") as f:
            return normalize(json.load(f), doc)
    if not isinstance(doc, dict):
        raise ValueError("cannot normalize %r from %s" % (type(doc), source))
    if isinstance(doc.get("metrics"), dict):            # already canonical
        out = dict(doc)
        out.setdefault("source", os.path.basename(source))
        return out
    if isinstance(doc.get("parsed"), dict):             # driver wrapper
        return _norm_bench_parsed(doc["parsed"], source)
    if "scaling_efficiency" in doc or "n_devices" in doc:
        return _norm_multichip(doc, source)
    if "throughput_chip_pending" in doc:                # bench.py --bf16
        return _norm_bench_bf16(doc, source)
    if "flops_per_token" in doc:                 # bench.py --transformer
        return _norm_bench_transformer(doc, source)
    if doc.get("bench") == "serving" or "sweep" in doc:
        return _norm_serving_gateway(doc, source)
    if "p99_ms" in doc or "latency_p99_ms" in doc or \
            "throughput_rps" in doc:
        return _norm_serving(doc, source)
    if "value" in doc or "mfu_pct" in doc:              # bare parsed block
        return _norm_bench_parsed(doc, source)
    # nothing recognizable: canonical-but-empty keeps the pipeline total
    return {"round": _round_of(source), "source": os.path.basename(source),
            "kind": "unknown", "metrics": {}, "context": {}}


def merge_rounds(rounds: List[dict]) -> dict:
    """Several normalized docs (bench + multichip + serving of one round)
    into one: later docs win metric collisions."""
    out = {"round": None, "source": [], "kind": "merged",
           "metrics": {}, "context": {}}
    for r in rounds:
        out["round"] = r.get("round") or out["round"]
        out["source"].append(r.get("source"))
        out["metrics"].update(r.get("metrics") or {})
        out["context"].update(r.get("context") or {})
    return out


# ---------------------------------------------------------------------------
# compare
# ---------------------------------------------------------------------------
def band_of(metric: str) -> Tuple[str, float, float]:
    return TOLERANCES.get(metric, DEFAULT_BAND)


def compare(baseline: dict, candidate: dict) -> List[dict]:
    """Verdict rows, ranked worst-first.  Both args are canonical round
    docs.  A metric present only in the candidate is informational
    (NEW); one that vanished is a WARN — silent metric loss is how
    regressions hide."""
    b_m = baseline.get("metrics") or {}
    c_m = candidate.get("metrics") or {}
    rows = []
    for name in sorted(set(b_m) | set(c_m)):
        b, c = _num(b_m.get(name)), _num(c_m.get(name))
        direction, rel, slack = band_of(name)
        band = max(rel * abs(b), slack) if b is not None else 0.0
        if b is None:
            rows.append({"metric": name, "baseline": None, "candidate": c,
                         "delta_pct": None, "band": band,
                         "verdict": "NEW", "excess": -1.0})
            continue
        if c is None:
            rows.append({"metric": name, "baseline": b, "candidate": None,
                         "delta_pct": None, "band": band,
                         "verdict": "MISSING", "excess": 0.5})
            continue
        delta = c - b
        delta_pct = (100.0 * delta / abs(b)) if b else None
        bad = -delta if direction == "higher" else delta
        if bad <= 0:
            verdict, excess = "PASS", -1.0
        elif band <= 0:
            verdict, excess = "FAIL", float("inf")  # zero-tolerance metric
        elif bad > band:
            verdict, excess = "FAIL", bad / band
        elif bad > 0.5 * band:
            verdict, excess = "WARN", bad / band
        else:
            verdict, excess = "PASS", bad / band
        rows.append({"metric": name, "baseline": b, "candidate": c,
                     "delta_pct": delta_pct, "band": band,
                     "verdict": verdict, "excess": excess})
    order = {"FAIL": 0, "WARN": 1, "MISSING": 2, "PASS": 3, "NEW": 4}
    rows.sort(key=lambda r: (order.get(r["verdict"], 9), -r["excess"],
                             r["metric"]))
    return rows


def verdict_exit(rows: List[dict]) -> int:
    return 1 if any(r["verdict"] == "FAIL" for r in rows) else 0


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float) and v == float("inf"):
        return "inf"
    return "%.4g" % v


def markdown_table(rows: List[dict], baseline: dict,
                   candidate: dict) -> str:
    def _name(doc, fallback):
        src = doc.get("source") or doc.get("round") or fallback
        if isinstance(src, (list, tuple)):
            src = "+".join(str(s) for s in src)
        return src

    lines = [
        "## sentinel verdict: %s vs baseline %s"
        % (_name(candidate, "candidate"), _name(baseline, "?")),
        "",
        "| metric | baseline | candidate | delta | band | verdict |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        direction, _, _ = band_of(r["metric"])
        arrow = "^" if direction == "higher" else "v"
        delta = ("%+.1f%%" % r["delta_pct"]
                 if r["delta_pct"] is not None else "-")
        lines.append("| %s (%s) | %s | %s | %s | %s | **%s** |" % (
            r["metric"], arrow, _fmt(r["baseline"]), _fmt(r["candidate"]),
            delta, _fmt(r["band"]), r["verdict"]))
    n_fail = sum(1 for r in rows if r["verdict"] == "FAIL")
    n_warn = sum(1 for r in rows if r["verdict"] == "WARN")
    lines += ["", "**%s** — %d FAIL, %d WARN, %d metrics compared"
              % ("REGRESSION" if n_fail else "OK", n_fail, n_warn,
                 len(rows))]
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# smoke: self-test the whole pipe on synthetic + committed data
# ---------------------------------------------------------------------------
def smoke() -> int:
    base = {"round": "rA", "source": "synthetic-base", "kind": "bench",
            "metrics": {"resnet50_img_per_sec": 2450.0,
                        "resnet50_mfu_pct": 30.6,
                        "resnet50_step_spread_pct": 0.7,
                        "lstm_tokens_per_sec": 460000.0},
            "context": {}}
    ok = True
    # identical runs must pass
    rows = compare(base, dict(base))
    ok &= verdict_exit(rows) == 0 and all(
        r["verdict"] == "PASS" for r in rows)
    # a ~20% throughput regression must FAIL, ranked first
    cand = json.loads(json.dumps(base))
    cand["metrics"]["resnet50_img_per_sec"] *= 0.8
    rows = compare(base, cand)
    ok &= verdict_exit(rows) == 1
    ok &= rows[0]["metric"] == "resnet50_img_per_sec" \
        and rows[0]["verdict"] == "FAIL"
    # a within-band wobble must not fail
    cand2 = json.loads(json.dumps(base))
    cand2["metrics"]["resnet50_img_per_sec"] *= 0.97
    ok &= verdict_exit(compare(base, cand2)) == 0
    # improvements always pass, even huge ones
    cand3 = json.loads(json.dumps(base))
    cand3["metrics"]["resnet50_img_per_sec"] *= 2.0
    cand3["metrics"]["resnet50_step_spread_pct"] = 0.0
    ok &= verdict_exit(compare(base, cand3)) == 0
    # the real committed record must normalize to non-empty metrics
    r05 = os.path.join(REPO, "BENCH_r05.json")
    if os.path.exists(r05):
        n = normalize(r05)
        ok &= bool(n["metrics"]) and \
            "resnet50_img_per_sec" in n["metrics"]
    if os.path.exists(DEFAULT_BASELINE):
        with open(DEFAULT_BASELINE) as f:
            bdoc = json.load(f)
        ok &= isinstance(bdoc.get("metrics"), dict) and bool(bdoc["metrics"])
        # two identical runs of the committed baseline must pass
        ok &= verdict_exit(compare(bdoc, bdoc)) == 0
    print(json.dumps({"probe": "sentinel", "ok": bool(ok)}))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="sentinel.py",
        description="perf regression gate over the canonical bench "
                    "trajectory")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="committed baseline round (canonical JSON)")
    ap.add_argument("--candidate", nargs="*", default=[],
                    help="candidate record(s): BENCH/MULTICHIP JSON, "
                         "runlog .jsonl, or canonical; several merge "
                         "into one round")
    ap.add_argument("--normalize", nargs="*", default=[],
                    help="normalize these files and write/print the "
                         "canonical docs instead of comparing")
    ap.add_argument("-o", "--out", default=None,
                    help="output dir (--normalize) or file")
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the merged candidate over --baseline "
                         "after comparing")
    ap.add_argument("--format", choices=("md", "json"), default="md")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test the normalize/compare pipeline")
    args = ap.parse_args(argv)

    if args.smoke:
        return smoke()

    if args.normalize:
        paths = [p for pat in args.normalize for p in
                 (sorted(glob.glob(pat)) or [pat])]
        docs = [normalize(p) for p in paths]
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            for d in docs:
                name = os.path.splitext(str(d.get("source")))[0].lower()
                dst = os.path.join(args.out, name + ".canonical.json")
                with open(dst, "w") as f:
                    json.dump(d, f, indent=1, sort_keys=True)
                    f.write("\n")
                print(dst)
        else:
            json.dump(docs if len(docs) > 1 else docs[0],
                      sys.stdout, indent=1, sort_keys=True)
            print()
        return 0

    if not args.candidate:
        ap.error("need --candidate (or --normalize / --smoke)")
    candidate = merge_rounds([normalize(p) for p in args.candidate])
    if not os.path.exists(args.baseline):
        sys.stderr.write("sentinel: no baseline at %s\n" % args.baseline)
        if args.update_baseline:
            os.makedirs(os.path.dirname(args.baseline) or ".",
                        exist_ok=True)
            with open(args.baseline, "w") as f:
                json.dump(candidate, f, indent=1, sort_keys=True)
                f.write("\n")
            sys.stderr.write("sentinel: seeded baseline from candidate\n")
            return 0
        return 2
    with open(args.baseline) as f:
        baseline = json.load(f)

    rows = compare(baseline, candidate)
    if args.format == "json":
        out = {"baseline": baseline.get("source"),
               "candidate": candidate.get("source"),
               "rows": rows, "regression": bool(verdict_exit(rows))}
        print(json.dumps(out, indent=1, sort_keys=True))
    else:
        sys.stdout.write(markdown_table(rows, baseline, candidate))

    rc = verdict_exit(rows)
    if args.update_baseline:
        if rc == 0:
            with open(args.baseline, "w") as f:
                json.dump(candidate, f, indent=1, sort_keys=True)
                f.write("\n")
            sys.stderr.write("sentinel: baseline updated\n")
        else:
            sys.stderr.write(
                "sentinel: refusing to update baseline over a FAIL "
                "(fix or edit %s manually)\n" % args.baseline)
    return rc


if __name__ == "__main__":
    sys.exit(main())
