"""Program Atlas: per-layer flop/byte attribution inside fused XLA programs.

The fused whole-step path (the default since PR 6) collapses forward,
backward and the optimizer update into ONE opaque XLA program, so the old
per-op executor spans attribute nothing and health.py (PR 7) reports only
whole-program aggregates.  The atlas recovers the per-layer breakdown
without giving up fusion, in two halves:

**Scope annotation (trace time).**  Every traced op application is wrapped
in ``jax.named_scope`` at the single choke points — the ``_Plan`` execution
loop and segment builder in executor.py, the op-apply wrapper in
ops/registry.py, and the optimizer/grad-sync stages of the step/update
program builders (executor.py / fused_step.py / fused.py).  The scope name
contract:

- ``<OpType>:<node_name>`` — one graph node's op application (e.g.
  ``Convolution:stage1_conv1``).  Eager per-op entries use the anonymous
  node ``~``.
- ``Optimizer::<Name>`` — one optimizer's fused update stage
  (:func:`optimizer_scope`; ``Optimizer.atlas_scope_name`` overrides).
- ``GradSync`` — the in-program gradient reduce (replica sum / mesh
  all-reduce) and, where the mesh step keeps the optimizer's state split
  over ``dp``, the all-gather of the updated weights.

jax carries these names into the lowered StableHLO as MLIR location
debug info, through ``jax.vjp`` as ``jvp(...)`` / ``transpose(jvp(...))``
wrappers — so a layer's scope owns its forward AND backward instructions.

**Attribution (lowering only).**  :func:`analyze` walks the MLIR text of a
program already lowered by health.register_program — ``compiler_ir()``
serialization, never a compile; the established lowering-only discipline
(AOT ``.compile()`` does not share the jit call cache on this jax, and
deep mode stays behind ``MXNET_HEALTH_DEEP``).  Instructions are grouped
by innermost scope; per-scope FLOPs come from the op dims
(``dot_general``: 2·out·K from the contracting dims; ``convolution``:
2·out·Cin/g·kh·kw from ``dim_numbers``; elementwise ≈ 1/elem), bytes from
the operand/result tensor types.  Calls into deduplicated private funcs
are charged to the CALL SITE's scope (the shared body carries only its
first caller's location).  Known limits, documented in
docs/observability.md: control-flow region bodies (``while``/``reduce``)
count as one instruction of their scope, and the flop model is an
approximation of ``cost_analysis()`` — coverage is reported, not assumed.

Consumers: ``tools/program_atlas.py`` (CLI: ``--top-k``, ``--format
json``, ``--diff``, ``--smoke``), the ``/programz`` telemetry endpoint,
``bench.py --atlas``, and flight-recorder dumps.

Gate: ``MXNET_ATLAS`` (default on; analysis only runs inside
health.register_program, which is itself off by default).
"""
from __future__ import annotations

import re
import threading

from . import telemetry as _telemetry
from .base import get_env

__all__ = ["enabled", "GRAD_SYNC", "scope_name", "optimizer_scope",
           "analyze", "analyze_text", "atlases", "get", "snapshot",
           "diff", "reset", "ScopeStat", "ProgramAtlas"]

#: analysis gate (annotation is unconditional — named scopes are free).
enabled: bool = get_env("MXNET_ATLAS", True, bool)

_ATLAS_COVERAGE = _telemetry.gauge(
    "atlas_scope_coverage_pct",
    "share of a program's cost_analysis flops attributed to named scopes",
    ("program",))
_ATLAS_SCOPES = _telemetry.gauge(
    "atlas_scopes",
    "distinct named scopes attributed inside a registered program",
    ("program",))
_ATLAS_FAILURES = _telemetry.counter(
    "atlas_analyze_failures_total",
    "program lowerings the atlas parser could not attribute")

# --------------------------------------------------------------------------
# scope-name contract
# --------------------------------------------------------------------------
GRAD_SYNC = "GradSync"

_SAN_RE = re.compile(r"[^A-Za-z0-9_.\-~]")


def _sanitize(s):
    return _SAN_RE.sub("_", str(s)) or "_"


def scope_name(op_type, node_name="~"):
    """``<OpType>:<node_name>`` scope of one op application.

    ``~`` is the anonymous node of eager per-op entries (ops/registry.py),
    where no graph node name exists."""
    return "%s:%s" % (_sanitize(op_type), _sanitize(node_name))


def part_scope(name):
    """A named part INSIDE one op's ``<OpType>:<node>`` scope (e.g.
    ``DeltaAttention``'s core, ``delta_rule``): a word without a colon, so
    the atlas still attributes the part's instructions to the op's node
    (a scope token has a colon), while a device trace can read the part
    alone by its name."""
    import jax
    if ":" in name:
        raise ValueError("a part's scope has no colon: %r" % (name,))
    return jax.named_scope(_sanitize(name))


def optimizer_scope(update_fn):
    """``Optimizer::<Name>`` scope of a (bound) fused_update stage."""
    owner = getattr(update_fn, "__self__", update_fn)
    name = None
    hook = getattr(owner, "atlas_scope_name", None)
    if callable(hook):
        try:
            name = hook()
        except Exception:
            name = None
    if not name:
        name = type(owner).__name__
    return "Optimizer::%s" % _sanitize(name)


# one regex, three alternatives, innermost (last) match wins: the token
# survives inside jvp(...)/transpose(jvp(...)) autodiff name wrappers
_SCOPE_TOKEN_RE = re.compile(
    r"Optimizer::[A-Za-z0-9_.\-~]+"
    r"|(?<![\w:])GradSync(?![\w:])"
    r"|[A-Za-z_][A-Za-z0-9_.\-]*:[A-Za-z0-9_.\-~]+")

# --------------------------------------------------------------------------
# MLIR location / type parsing
# --------------------------------------------------------------------------
_LOCDEF_RE = re.compile(r"^\s*#loc(\d*)\s*=\s*loc\((.*)\)\s*$")
_LOCREF_IN_DEF_RE = re.compile(r"#loc(\d*)")
_LOCREF_RE = re.compile(r"loc\((?:#loc(\d*)|unknown)\)\s*$")
_FUNC_RE = re.compile(r"func\.func\b[^@]*@([\w$.\-]+)")
_TYPE_RE = re.compile(r"tensor<((?:[^<>]|<[^<>]*>)*)>")
_CALLEE_RE = re.compile(r"@([\w$.\-]+)")
_RESULT_RE = re.compile(r"^\s*%[\w]+(?::\d+)?\s*=\s*(.*)$")
_OPNAME_RE = re.compile(r'^"?([A-Za-z_][\w.]*)"?')

_ITEMSIZE = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3FNUZ": 1, "f8E5M2FNUZ": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i4": 1, "ui4": 1, "i1": 1, "pred": 1,
    "complex<f32>": 8, "complex<f64>": 16,
}

#: pure data movement / bookkeeping: bytes count, zero flops
_ZERO_FLOP = frozenset((
    "reshape", "transpose", "broadcast_in_dim", "broadcast", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "convert",
    "bitcast_convert", "constant", "iota", "reverse", "pad", "gather",
    "copy", "tuple", "get_tuple_element", "optimization_barrier",
    "custom_call", "after_all", "create_token", "rng_bit_generator",
    "return", "real", "imag", "composite", "all_gather", "collective_permute",
))

#: ops whose cost scales with the INPUT, not the output
_REDUCE_OPS = frozenset((
    "reduce", "reduce_window", "select_and_scatter", "sort", "scatter",
    "all_reduce", "reduce_scatter",
))


def _parse_type(text):
    """``"2x3xf32"`` -> ((2, 3), itemsize). Dynamic dims count as 1."""
    parts = text.split("x")
    dtype = parts[-1]
    dims = []
    for p in parts[:-1]:
        p = p.strip()
        dims.append(int(p) if p.isdigit() else 1)
    return tuple(dims), _ITEMSIZE.get(dtype.strip(), 4)


def _numel(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def _paren_delta(line):
    """Net '(' depth change, ignoring parens inside string literals."""
    d, instr, i, n = 0, False, 0, len(line)
    while i < n:
        c = line[i]
        if instr:
            if c == "\\":
                i += 1
            elif c == '"':
                instr = False
        elif c == '"':
            instr = True
        elif c == "(":
            d += 1
        elif c == ")":
            d -= 1
        i += 1
    return d


def _brace_delta(line):
    d, instr, i, n = 0, False, 0, len(line)
    while i < n:
        c = line[i]
        if instr:
            if c == "\\":
                i += 1
            elif c == '"':
                instr = False
        elif c == '"':
            instr = True
        elif c == "{":
            d += 1
        elif c == "}":
            d -= 1
        i += 1
    return d


def _logical_lines(text):
    """Join physical lines until parens balance: a region op
    (``reduce``/``while`` ``({ ... })``) becomes ONE logical instruction
    attributed to the region's own scope."""
    out, buf, depth = [], "", 0
    for raw in text.splitlines():
        stripped = raw.strip()
        if not stripped:
            continue
        buf = (buf + " " + stripped) if buf else stripped
        depth += _paren_delta(stripped)
        if depth <= 0:
            out.append(buf)
            buf, depth = "", 0
    if buf:
        out.append(buf)
    return out


def _build_loc_scopes(text):
    """locid -> innermost scope token (or None) from the ``#locN = loc(...)``
    debug-info table; alias/callsite locs resolve through their refs."""
    raw = {}
    for line in text.splitlines():
        m = _LOCDEF_RE.match(line)
        if m:
            raw[m.group(1)] = m.group(2)
    memo = {}

    def resolve(lid, depth=0):
        if lid in memo:
            return memo[lid]
        memo[lid] = None  # cycle guard
        rhs = raw.get(lid)
        if rhs is None or depth > 8:
            return None
        toks = _SCOPE_TOKEN_RE.findall(rhs)
        if toks:
            memo[lid] = toks[-1]
            return memo[lid]
        for ref in _LOCREF_IN_DEF_RE.findall(rhs):
            if ref != lid:
                s = resolve(ref, depth + 1)
                if s is not None:
                    memo[lid] = s
                    return s
        return None

    return {lid: resolve(lid) for lid in raw}


def _split_funcs(lines):
    """Logical lines -> {func_name: [body lines]} in definition order."""
    funcs = {}
    order = []
    cur, body, depth = None, None, 0
    for ln in lines:
        if cur is None:
            m = _FUNC_RE.search(ln)
            if m and _brace_delta(ln) > 0:
                cur, body, depth = m.group(1), [], _brace_delta(ln)
            continue
        depth += _brace_delta(ln)
        if depth <= 0:
            funcs[cur] = body
            order.append(cur)
            cur, body = None, None
        else:
            body.append(ln)
    if cur is not None:
        funcs[cur] = body
        order.append(cur)
    return funcs, order


def _dot_flops(rest, ins, outs):
    m = (re.search(r"contracting_dims\s*=\s*\[([\d\s,]*)\]", rest)
         or re.search(r"lhs_contracting_dimensions\s*=\s*\[([\d\s,]*)\]",
                      rest))
    out_n = _numel(outs[0][0]) if outs else 0
    if not m or not ins:
        return 2.0 * out_n * (ins[0][0][-1] if ins and ins[0][0] else 1)
    lhs_dims = ins[0][0]
    k = 1
    for tok in m.group(1).split(","):
        tok = tok.strip()
        if tok.isdigit() and int(tok) < len(lhs_dims):
            k *= lhs_dims[int(tok)]
    return 2.0 * out_n * k


def _conv_flops(rest, ins, outs):
    out_n = _numel(outs[0][0]) if outs else 0
    m = re.search(r"x\[([^\]]*)\]\s*->", rest)
    if not m or len(ins) < 2:
        return float(out_n)
    rhs_spec = [t.strip() for t in m.group(1).split(",")]
    rhs_dims = ins[1][0]
    if len(rhs_spec) != len(rhs_dims):
        return float(out_n)
    k = 1
    for spec, d in zip(rhs_spec, rhs_dims):
        if spec != "o":  # kernel spatial dims AND the (per-group) i dim
            k *= d
    return 2.0 * out_n * k


def _op_cost(short, rest, ins, outs, n_operands):
    """(flops, bytes) of one instruction from its parsed types."""
    out_bytes = sum(_numel(d) * isz for d, isz in outs)
    if ins is None:  # elementwise shorthand: operands typed like the result
        in_bytes = out_bytes * n_operands
        ins_eff = [outs[0]] if outs else []
    else:
        in_bytes = sum(_numel(d) * isz for d, isz in ins)
        ins_eff = ins
    nbytes = out_bytes + in_bytes
    if short in _ZERO_FLOP:
        return 0.0, nbytes
    if short in ("dot_general", "dot"):
        return _dot_flops(rest, ins_eff, outs), nbytes
    if short == "convolution":
        return _conv_flops(rest, ins_eff, outs), nbytes
    if short in _REDUCE_OPS:
        n = _numel(ins_eff[0][0]) if ins_eff else 0
        return float(n), nbytes
    return float(_numel(outs[0][0]) if outs else 0), nbytes


def _parse_instr(ln):
    """One logical op line -> (short_op, callee, rest, ins, outs,
    n_operands, locid) or None for non-instructions."""
    m = _LOCREF_RE.search(ln)
    locid = m.group(1) if m and m.group(1) is not None else None
    body = ln[: m.start()].rstrip() if m else ln
    rm = _RESULT_RE.match(body)
    rest = rm.group(1) if rm else body.strip()
    om = _OPNAME_RE.match(rest)
    if not om:
        return None
    opname = om.group(1)
    short = opname.split(".")[-1]
    if short in ("func", "module", "return"):
        return None
    callee = None
    if short == "call":
        cm = _CALLEE_RE.search(rest)
        callee = cm.group(1) if cm else None
    # last " : " is the function-type signature (attr types like
    # ``1 : i64`` always precede it)
    parts = rest.rsplit(" : ", 1)
    ins = outs = None
    if len(parts) == 2:
        sig = parts[1]
        arrow = sig.rfind("->")
        if arrow >= 0:
            ins = [_parse_type(t) for t in _TYPE_RE.findall(sig[:arrow])]
            outs = [_parse_type(t) for t in _TYPE_RE.findall(sig[arrow:])]
        else:
            outs = [_parse_type(t) for t in _TYPE_RE.findall(sig)]
    n_operands = len(re.findall(r"%[A-Za-z0-9_]", parts[0]))
    return short, callee, parts[0], ins, outs or [], n_operands, locid


# --------------------------------------------------------------------------
# aggregation
# --------------------------------------------------------------------------
class ScopeStat:
    """Accumulated cost of one named scope inside one program."""

    __slots__ = ("scope", "flops", "bytes", "instructions", "calls")

    def __init__(self, scope):
        self.scope = scope
        self.flops = 0.0
        self.bytes = 0
        self.instructions = 0
        self.calls = 0

    def add(self, flops, nbytes, instructions=1, calls=0):
        self.flops += flops
        self.bytes += nbytes
        self.instructions += instructions
        self.calls += calls

    def as_dict(self):
        return {"scope": self.scope, "flops": self.flops,
                "bytes": self.bytes, "instructions": self.instructions,
                "calls": self.calls}


class _FuncSummary:
    """Per-function roll-up; private callee costs fold into call sites."""

    def __init__(self):
        self.by_scope = {}  # scope (str|None) -> ScopeStat

    def stat(self, scope):
        s = self.by_scope.get(scope)
        if s is None:
            s = self.by_scope[scope] = ScopeStat(scope)
        return s

    def merge(self, other):
        for scope, st in other.by_scope.items():
            self.stat(scope).add(st.flops, st.bytes, st.instructions,
                                 st.calls)

    def totals(self):
        f = b = i = 0
        for st in self.by_scope.values():
            f += st.flops
            b += st.bytes
            i += st.instructions
        return f, b, i


class ProgramAtlas:
    """Ranked per-scope attribution of one lowered program."""

    __slots__ = ("name", "total_flops", "parsed_flops", "scoped_flops",
                 "scopes", "unattributed", "n_instructions")

    def __init__(self, name, total_flops, by_scope):
        self.name = name
        self.scopes = {s: st for s, st in by_scope.items() if s is not None}
        self.unattributed = by_scope.get(None) or ScopeStat(None)
        self.scoped_flops = sum(st.flops for st in self.scopes.values())
        self.parsed_flops = self.scoped_flops + self.unattributed.flops
        # cost_analysis is the honest denominator when present; fall back
        # to the parsed total so standalone text analysis still ranks
        self.total_flops = float(total_flops or 0.0) or self.parsed_flops
        self.n_instructions = (self.unattributed.instructions
                               + sum(st.instructions
                                     for st in self.scopes.values()))

    def coverage(self):
        """Scoped share of the program's cost_analysis flops, in [0, ~1+]
        (the parsed model may slightly over/under-count vs XLA's)."""
        if self.total_flops <= 0:
            return 1.0 if not self.parsed_flops else 0.0
        return self.scoped_flops / self.total_flops

    def table(self, top_k=None):
        """Ranked rows (flops desc), shares against the program total."""
        denom_f = max(self.total_flops, self.parsed_flops, 1.0)
        denom_b = max(self.unattributed.bytes
                      + sum(st.bytes for st in self.scopes.values()), 1)
        rows = []
        for st in sorted(self.scopes.values(),
                         key=lambda s: (-s.flops, -s.bytes, s.scope)):
            d = st.as_dict()
            d["flops_share"] = st.flops / denom_f
            d["bytes_share"] = st.bytes / denom_b
            rows.append(d)
        return rows[:top_k] if top_k else rows

    def as_dict(self, top_k=None):
        return {"program": self.name,
                "total_flops": self.total_flops,
                "parsed_flops": self.parsed_flops,
                "scoped_flops": self.scoped_flops,
                "coverage_pct": round(100.0 * self.coverage(), 2),
                "n_scopes": len(self.scopes),
                "n_instructions": self.n_instructions,
                "unattributed": self.unattributed.as_dict(),
                "scopes": self.table(top_k)}


def analyze_text(name, asm, cost_flops=None):
    """Pure attribution of one MLIR module text (no jax imports): the
    testable core of :func:`analyze`."""
    loc_scopes = _build_loc_scopes(asm)
    funcs, order = _split_funcs(_logical_lines(asm))
    summaries = {}

    def summarize(fname, stack=()):
        if fname in summaries:
            return summaries[fname]
        if fname in stack or len(stack) > 16:
            return _FuncSummary()
        summary = _FuncSummary()
        for ln in funcs.get(fname, ()):
            parsed = _parse_instr(ln)
            if parsed is None:
                continue
            short, callee, rest, ins, outs, n_ops, locid = parsed
            scope = loc_scopes.get(locid) if locid is not None else None
            if short == "call" and callee in funcs:
                sub = summarize(callee, stack + (fname,))
                if scope is not None:
                    # dedup hazard: a shared private func body carries only
                    # its FIRST caller's locations — charge the call site
                    f, b, i = sub.totals()
                    summary.stat(scope).add(f, b, i, calls=1)
                else:
                    summary.merge(sub)
                    summary.stat(None).calls += 1
                continue
            flops, nbytes = _op_cost(short, rest, ins, outs, n_ops)
            summary.stat(scope).add(flops, nbytes)
        summaries[fname] = summary
        return summary

    entry = "main" if "main" in funcs else (order[0] if order else None)
    top = summarize(entry) if entry else _FuncSummary()
    return ProgramAtlas(name, cost_flops, top.by_scope)


# --------------------------------------------------------------------------
# program registry (fed by health.register_program)
# --------------------------------------------------------------------------
_atlases = {}
_atlases_lock = threading.Lock()


def analyze(name, lowered, cost_flops=None):
    """Attribute one ``jax.stages.Lowered`` and register the result.

    Serialization only — ``compiler_ir().operation.get_asm`` never
    touches XLA, so the zero-extra-compile contract of the health
    registration path holds.  Returns the :class:`ProgramAtlas` or None
    (disabled / unparsable — the atlas must never break registration)."""
    if not enabled:
        return None
    try:
        op = lowered.compiler_ir().operation
        try:
            asm = op.get_asm(enable_debug_info=True, large_elements_limit=16)
        except TypeError:
            asm = op.get_asm(enable_debug_info=True)
        if cost_flops is None:
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            cost_flops = float((cost or {}).get("flops", 0.0) or 0.0)
        atl = analyze_text(name, asm, cost_flops)
    except Exception:
        _ATLAS_FAILURES.inc()
        return None
    with _atlases_lock:
        _atlases[name] = atl
    _ATLAS_COVERAGE.labels(program=name).set(100.0 * atl.coverage())
    _ATLAS_SCOPES.labels(program=name).set(len(atl.scopes))
    return atl


def atlases():
    """Snapshot of every analyzed program's atlas."""
    with _atlases_lock:
        return dict(_atlases)


def get(name):
    with _atlases_lock:
        return _atlases.get(name)


def snapshot(top_k=None):
    """JSON-able {program: atlas dict} — the /programz payload shape."""
    return {n: a.as_dict(top_k) for n, a in sorted(atlases().items())}


def reset():
    """Test isolation: drop every analyzed program."""
    with _atlases_lock:
        _atlases.clear()


# --------------------------------------------------------------------------
# before/after diff (CLI --diff)
# --------------------------------------------------------------------------
def diff(a, b):
    """Per-scope flop/byte deltas between two :func:`snapshot` documents
    (``{program: {"scopes": [...], ...}}``), ranked by |delta flops| —
    the before/after attribution of a perf change.  Rows:
    ``{program, scope, flops_a, flops_b, delta_flops, delta_bytes}``."""
    rows = []
    for prog in sorted(set(a) | set(b)):
        sa = {r["scope"]: r for r in (a.get(prog) or {}).get("scopes", ())}
        sb = {r["scope"]: r for r in (b.get(prog) or {}).get("scopes", ())}
        for scope in sorted(set(sa) | set(sb)):
            ra, rb = sa.get(scope), sb.get(scope)
            fa = float(ra["flops"]) if ra else 0.0
            fb = float(rb["flops"]) if rb else 0.0
            ba = int(ra.get("bytes", 0)) if ra else 0
            bb = int(rb.get("bytes", 0)) if rb else 0
            if fa == fb and ba == bb:
                continue
            rows.append({"program": prog, "scope": scope,
                         "flops_a": fa, "flops_b": fb,
                         "delta_flops": fb - fa,
                         "delta_bytes": bb - ba})
    rows.sort(key=lambda r: (-abs(r["delta_flops"]),
                             -abs(r["delta_bytes"]),
                             r["program"], r["scope"]))
    return rows
