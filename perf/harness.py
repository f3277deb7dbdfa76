"""What every loop of the benchmark shares: finding a cell's files by name,
the device check, jax's own compile events, host spans, the per-layer
readers, and the result line."""
import contextlib
import dataclasses
import importlib
import json
import os
import sys
import time


# ------------------------------------------------------------ files by name
def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(kind, name):
    """``perf/<kind>/<name>.py``."""
    return importlib.import_module("perf.%s.%s" % (kind, name))


@dataclasses.dataclass
class Cell:
    manifest: dict
    entry: dict          # the cell's entry in BENCHMARK.json
    workload: dict       # perf/workloads/<cell>.json
    config: dict         # perf/configs/<config>.json
    builder: object      # perf/models/<config>.py


def cell_entry(root, name):
    """The cell's entry in ``BENCHMARK.json``."""
    manifest = read_json(root, "BENCHMARK.json")
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit("no cell %r in BENCHMARK.json" % (name,))
    return entries[0]


def load_cell(root, name, rehearse=False):
    manifest = read_json(root, "BENCHMARK.json")
    entry = cell_entry(root, name)
    workload = read_json(root, "perf", "workloads", name + ".json")
    cfg_entry = [c for c in manifest["configs"] if c["name"] == entry["config"]][0]
    config = read_json(root, cfg_entry["file"])
    for key in ("config", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit("%s: %r in the workload file is not the "
                             "manifest's" % (name, key))
    if rehearse:
        tiny = dict(workload["rehearse"])
        config = {**config, **tiny.pop("config", {})}
        workload = {**workload, **tiny}
    return Cell(manifest, entry, workload, config,
                by_name("models", entry["config"]))


@dataclasses.dataclass
class Run:
    cell: Cell
    args: object
    devices: list
    t_start: float
    root: str


# ------------------------------------------------------------------ device
def pin_cpu(chips):
    """The rehearsal's devices: the CPU, as many virtual ones as chips."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % max(chips, 1)).strip()


def devices_or_none(chips, platform):
    """The first ``chips`` devices, or None (said on stderr) where jax finds
    another platform or fewer of them."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.stderr.write("perf/run.py: jax found no device: %s\n" % (e,))
        return None
    if devices[0].platform != platform or len(devices) < chips:
        sys.stderr.write(
            "perf/run.py: the cell needs %d %s device(s); jax reports %d %s\n"
            % (chips, platform, len(devices), devices[0].platform))
        return None
    return devices[:chips]


def peaks(root, device_kind):
    table = read_json(root, "perf", "peaks.json")
    if device_kind not in table["device_kind"]:
        raise KeyError("no peaks for device kind %r in perf/peaks.json"
                       % (device_kind,))
    return table["device_kind"][device_kind]


def memory_peak_bytes(devices):
    """What the fullest chip held at its fullest, from ``memory_stats()``:
    the high-water mark of live arrays (``peak_bytes_in_use``) plus that of
    the space the runtime reserves at the bottom of memory for the loaded
    programs' temporaries (``peak_bytes_reserved``).  On the TPU the two are
    disjoint: a step's temporaries never pass through ``bytes_in_use`` (PR 24's
    probe: ResNet-50 b256 reads 1.12 GB in use and 8.78 GB reserved, against
    the compiler's own 8.82 GB of temporaries)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


# ----------------------------------------------------------- compile events
class CompileWatch:
    """Counts what jax itself reports: every XLA compile request of the
    process (fresh or restored from the persistent cache), the seconds they
    took, and the persistent-cache hits among them.  (Copied from
    ``chip_smoke.py``: the yardstick may not change with the program.)"""

    _REQUEST = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_):
        if event == self._REQUEST:
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def mark(self):
        return (self.requests, self.seconds, self.hits)

    def since(self, mark):
        req = self.requests - mark[0]
        hits = self.hits - mark[2]
        return {"requests": req, "fresh": req - hits, "cache_hits": hits,
                "seconds": self.seconds - mark[1]}


# -------------------------------------------------------------- host spans
class Spans:
    """The harness's own spans around its calls into the program, on the
    host clock; while a trace is on, each is written into the profiler's
    trace too (``jax.profiler.TraceAnnotation``), so that an idle gap of the
    device can be laid to what the host was doing."""

    def __init__(self):
        self.rows = []           # (name, start, end) in perf_counter seconds
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name):
        note = None
        if self.annotate:
            import jax
            note = jax.profiler.TraceAnnotation("perf:" + name)
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))
            if note is not None:
                note.__exit__(None, None, None)

    def between(self, name, lo, hi):
        return [(s, e) for n, s, e in self.rows if n == name and lo <= s <= hi]


# ------------------------------------------------------------------ numbers
def percentile(values, q):
    """The q-th percentile by linear interpolation (numpy's default)."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


# ------------------------------------------------------------- result line
def per_layer_metrics(run, ctx):
    """Every per-layer metric the manifest lists for this cell, each read by
    the reader its own file names; a reader that finds nothing to read
    returns None and the metric is left out."""
    cell = run.cell
    out = {}
    for m in cell.manifest["per_layer"]:
        if "workloads" in m and cell.entry["name"] not in m["workloads"]:
            continue
        spec = read_json(run.root, "perf", "metrics", m["name"] + ".json")
        value = by_name("reducers", spec["reducer"]).read(
            ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(*, correct, attempted, failed, metrics, device, check,
         breakdown=None, extra=None):
    """The numbers compared, each beside its limit, as the last lines of
    standard error; then the result as the last line of standard output,
    with the same numbers under ``check``, which comes last."""
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["check"] = check
    sys.stdout.flush()
    for name, row in check.items():
        sys.stderr.write("check %s %s\n" % (name, json.dumps(row)))
    sys.stderr.write("correct %s\n" % (bool(correct),))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
