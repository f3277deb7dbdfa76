"""Symbol: declarative graph construction, composition, inference, binding.

Reference analog: ``python/mxnet/symbol/symbol.py`` over the NNVM graph IR
(``3rdparty/tvm`` nnvm: Node/NodeEntry/Symbol; passes Gradient/PlanMemory —
SURVEY.md N6/N19).  TPU-native design: the graph is a lightweight Python DAG
over the op registry; *binding* lowers it to a pure JAX function that XLA
compiles whole (fusion + memory planning + layout all delegated to XLA — the
PlanMemory/AttachOpExecs pass pipeline of graph_executor.cc:514-905 collapses
into one jit).  Gradient graphs come from jax.vjp of that function rather than
an nnvm Gradient pass.  JSON (de)serialization keeps the reference's
``nodes/arg_nodes/heads`` format so checkpoints interchange.
"""
from __future__ import annotations

import json
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..base import MXNetError, AttrDict
from ..context import Context, current_context
from ..ops.registry import get_op, Operator, OPS

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "zeros", "ones", "arange"]


class _Node:
    __slots__ = ("op", "name", "attrs", "inputs", "__weakref__")

    def __init__(self, op: Optional[Operator], name: str,
                 attrs: Dict[str, Any], inputs: List[Tuple["_Node", int]]):
        self.op = op
        self.name = name
        self.attrs = attrs          # raw user attrs (JSON-serializable)
        self.inputs = inputs

    @property
    def is_var(self):
        return self.op is None

    def parsed_attrs(self) -> AttrDict:
        a = {k: v for k, v in self.attrs.items() if not k.startswith("__")}
        return self.op.parse_attrs(a)

    def num_outputs(self):
        return 1 if self.is_var else self.op.num_outputs(self.parsed_attrs())

    def num_visible(self):
        return 1 if self.is_var else \
            self.op.num_visible_outputs(self.parsed_attrs())


def _auto_name(prefix: str) -> str:
    from ..name import current_scope
    return current_scope().get(None, prefix)


class Symbol:
    """A set of output entries of a graph (parity: mxnet.symbol.Symbol)."""

    __slots__ = ("_outputs",)

    def __init__(self, outputs: List[Tuple[_Node, int]]):
        self._outputs = outputs

    # ---- basic info -----------------------------------------------------
    @property
    def name(self):
        if len(self._outputs) == 1:
            return self._outputs[0][0].name
        return None

    def __repr__(self):
        return "<Symbol %s>" % (self.name or "group [%d]" % len(self._outputs))

    def __iter__(self):
        for i in range(len(self.list_outputs())):
            yield self[i]

    def __len__(self):
        return len(self._outputs)

    def _topo(self) -> List[_Node]:
        order, seen = [], set()
        stack = [(n, False) for n, _ in reversed(self._outputs)]
        while stack:
            node, done = stack.pop()
            if done:
                order.append(node)
                continue
            if id(node) in seen:
                continue
            seen.add(id(node))
            stack.append((node, True))
            for parent, _ in reversed(node.inputs):
                if id(parent) not in seen:
                    stack.append((parent, False))
        return order

    def _aux_var_ids(self) -> set:
        aux = set()
        for node in self._topo():
            if node.is_var or not node.op.aux_inputs:
                continue
            for i in node.op.aux_inputs:
                if i < len(node.inputs) and node.inputs[i][0].is_var:
                    aux.add(id(node.inputs[i][0]))
        return aux

    def list_arguments(self) -> List[str]:
        aux = self._aux_var_ids()
        return [n.name for n in self._topo() if n.is_var and id(n) not in aux]

    def list_auxiliary_states(self) -> List[str]:
        aux = self._aux_var_ids()
        return [n.name for n in self._topo() if n.is_var and id(n) in aux]

    def list_outputs(self) -> List[str]:
        names = []
        for node, idx in self._outputs:
            if node.is_var:
                names.append(node.name)
            elif node.num_visible() > 1 or node.num_outputs() > 1:
                names.append("%s_output%d" % (node.name, idx))
            else:
                names.append("%s_output" % node.name)
        return names

    def list_inputs(self):
        return [n.name for n in self._topo() if n.is_var]

    @property
    def outputs(self):
        return self.list_outputs()

    def get_internals(self) -> "Symbol":
        outs = []
        for node in self._topo():
            for i in range(node.num_visible()):
                outs.append((node, i))
        return Symbol(outs)

    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                # also allow internals lookup by name
                internals = self.get_internals()
                inames = internals.list_outputs()
                if index in inames:
                    return internals[inames.index(index)]
                raise MXNetError("output %r not found; have %s" % (index, names))
            index = names.index(index)
        if isinstance(index, slice):
            return Symbol(self._outputs[index])
        return Symbol([self._outputs[index]])

    def attr(self, key):
        if len(self._outputs) == 1:
            return self._outputs[0][0].attrs.get(key)
        return None

    def attr_dict(self):
        out = {}
        for node in self._topo():
            if node.attrs:
                out[node.name] = {k: str(v) for k, v in node.attrs.items()}
        return out

    def _set_attr(self, **kwargs):
        for node, _ in self._outputs:
            node.attrs.update(kwargs)

    # ---- composition / arithmetic --------------------------------------
    def __call__(self, *args, **kwargs):
        """Compose: replace free variables with the given symbols."""
        self._compose(*args, **kwargs)
        return self

    def _compose(self, *args, **kwargs):
        mapping = {}
        if args:
            arg_names = self.list_arguments()
            for name_, s in zip(arg_names, args):
                mapping[name_] = s
        mapping.update(kwargs)
        replace = {}
        for node in self._topo():
            if node.is_var and node.name in mapping:
                rep = mapping[node.name]
                if len(rep._outputs) != 1:
                    raise MXNetError("can only compose with single-output symbols")
                replace[id(node)] = rep._outputs[0]
        for node in self._topo():
            node.inputs = [replace.get(id(p), (p, i)) for p, i in node.inputs]
        self._outputs = [replace.get(id(n), (n, i)) for n, i in self._outputs]

    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _create(op, [a, b], {})
        if isinstance(other, (int, float)):
            return _create(scalar_op, [self], {"scalar": float(other)})
        raise TypeError("unsupported operand type %s" % type(other))

    def __add__(self, other):
        return self._binary(other, "elemwise_add" if isinstance(other, Symbol)
                            else "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        if isinstance(other, (int, float)):
            return _create("_rminus_scalar", [self], {"scalar": float(other)})
        return self._binary(other, "broadcast_sub", None, reverse=True)

    def __mul__(self, other):
        return self._binary(other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        if isinstance(other, (int, float)):
            return _create("_rdiv_scalar", [self], {"scalar": float(other)})
        return self._binary(other, "broadcast_div", None, reverse=True)

    def __pow__(self, other):
        return self._binary(other, "broadcast_power", "_power_scalar")

    def __neg__(self):
        return _create("negative", [self], {})

    # method forms mirroring NDArray
    def reshape(self, shape, **kw):
        return _create("Reshape", [self], {"shape": shape, **kw})

    def flatten(self):
        return _create("Flatten", [self], {})

    def transpose(self, axes=()):
        return _create("transpose", [self], {"axes": axes})

    def sum(self, axis=None, keepdims=False):
        return _create("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False):
        return _create("mean", [self], {"axis": axis, "keepdims": keepdims})

    def slice_axis(self, axis, begin, end):
        return _create("slice_axis", [self],
                       {"axis": axis, "begin": begin, "end": end})

    def astype(self, dtype):
        return _create("Cast", [self], {"dtype": np.dtype(dtype).name})

    # ---- inference ------------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except MXNetError:
            raise

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        """Two-phase inference (the InferShape pass, SURVEY.md N6):
        forward-fill via jax.eval_shape + per-op shape hints for unknown
        parameter shapes."""
        arg_names = self.list_arguments()
        known: Dict[str, tuple] = {}
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        known.update({k: tuple(v) for k, v in kwargs.items() if v is not None})

        topo = self._topo()
        shapes: Dict[Tuple[int, int], Optional[tuple]] = {}
        for node in topo:
            if not node.is_var:
                continue
            if node.name in known:
                shapes[(id(node), 0)] = known[node.name]
            elif node.attrs.get("__shape__") is not None:
                # declared shape on the Variable (reference symbol.py var
                # shape attr participates in InferShape); 0-dims mean
                # "unknown, infer me" (gluon deferred init) — don't seed those.
                # After a tojson round-trip the attr arrives as its string
                # repr ("(1, 2)"), so parse before iterating.
                declared = node.attrs["__shape__"]
                if isinstance(declared, str):
                    import ast
                    declared = ast.literal_eval(declared)
                declared = tuple(declared)
                if all(d > 0 for d in declared):
                    shapes[(id(node), 0)] = declared

        import jax

        for _pass in range(3):
            changed = False
            for node in topo:
                if node.is_var:
                    continue
                attrs = node.parsed_attrs()
                in_sh = [shapes.get((id(p), i)) for p, i in node.inputs]
                if node.op.shape_hint is not None and any(
                        s is None for s in in_sh):
                    filled = node.op.shape_hint(attrs, in_sh)
                    for (p, pi), s in zip(node.inputs, filled):
                        if s is not None and shapes.get((id(p), pi)) is None:
                            shapes[(id(p), pi)] = tuple(s)
                            changed = True
                    in_sh = [shapes.get((id(p), i)) for p, i in node.inputs]
                if all(s is not None for s in in_sh) and \
                        shapes.get((id(node), 0)) is None:
                    out_sh = _abstract_node(node, attrs, in_sh)
                    for i, s in enumerate(out_sh):
                        shapes[(id(node), i)] = s
                    changed = True
            if not changed:
                break

        aux_names = self.list_auxiliary_states()
        var_shapes = {n.name: shapes.get((id(n), 0))
                      for n in topo if n.is_var}
        arg_shapes = [var_shapes.get(n) for n in arg_names]
        aux_shapes = [var_shapes.get(n) for n in aux_names]
        out_shapes = [shapes.get((id(n), i)) for n, i in self._outputs]
        if not partial and (any(s is None for s in arg_shapes) or
                            any(s is None for s in out_shapes)):
            missing = [n for n, s in zip(arg_names, arg_shapes) if s is None]
            raise MXNetError("infer_shape incomplete; unknown args: %s"
                             % missing)
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        """Default-everything-float32 type inference (the reference's
        InferType pass); explicit dtypes propagate forward."""
        arg_names = self.list_arguments()
        known = {}
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    known[n] = np.dtype(t)
        known.update({k: np.dtype(v) for k, v in kwargs.items()
                      if v is not None})
        arg_types = [known.get(n, np.float32) for n in arg_names]
        out_types = [np.float32] * len(self._outputs)
        aux_types = [np.float32] * len(self.list_auxiliary_states())
        return arg_types, out_types, aux_types

    # ---- serialization --------------------------------------------------
    def tojson(self) -> str:
        """Reference-compatible graph JSON (nodes/arg_nodes/heads —
        the format Symbol.save writes and legacy_json_util.cc upgrades)."""
        topo = self._topo()
        nid = {id(n): i for i, n in enumerate(topo)}
        nodes = []
        for n in topo:
            nodes.append({
                "op": "null" if n.is_var else n.op.name,
                "name": n.name,
                "attrs": {k: str(v) for k, v in n.attrs.items()},
                "inputs": [[nid[id(p)], i, 0] for p, i in n.inputs],
            })
        arg_nodes = [i for i, n in enumerate(topo) if n.is_var]
        heads = [[nid[id(n)], i, 0] for n, i in self._outputs]
        return json.dumps({"nodes": nodes, "arg_nodes": arg_nodes,
                           "node_row_ptr": list(range(len(topo) + 1)),
                           "heads": heads,
                           "attrs": {"mxnet_version": ["int", 10200]}},
                          indent=2)

    def save(self, fname: str):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ---- binding --------------------------------------------------------
    def simple_bind(self, ctx: Optional[Context] = None, grad_req="write",
                    type_dict=None, stype_dict=None, group2ctx=None,
                    shared_arg_names=None, shared_exec=None,
                    shared_buffer=None, _inferred=None, **kwargs):
        """Infer shapes from the given input shapes, allocate all arrays,
        return a bound Executor (ref: symbol.py:1552 → GraphExecutor::Init).

        ``_inferred`` is ``infer_shape(**kwargs)``'s result from an earlier
        bind of this symbol at these input shapes: a group that binds one
        graph on several devices walks it once, not once a device."""
        from ..executor import Executor
        from .. import ndarray as nd
        ctx = ctx or current_context()
        arg_shapes, _, aux_shapes = _inferred or self.infer_shape(**kwargs)
        arg_types, _, aux_types = self.infer_type(**(type_dict or {}))
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        args = {n: nd.zeros(s, ctx=ctx, dtype=t)
                for n, s, t in zip(arg_names, arg_shapes, arg_types)}
        auxs = {n: nd.zeros(s, ctx=ctx, dtype=t)
                for n, s, t in zip(aux_names, aux_shapes, aux_types)}
        req = _norm_grad_req(grad_req, arg_names)
        grads = {n: nd.zeros(s, ctx=ctx, dtype=t)
                 for n, s, t in zip(arg_names, arg_shapes, arg_types)
                 if req.get(n, "null") != "null"}
        return Executor(self, ctx, args, grads, req, auxs,
                        group2ctx=group2ctx)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        """Bind with user-provided arrays (ref: symbol.py:1288)."""
        from ..executor import Executor
        from .. import ndarray as nd
        ctx = ctx or current_context()
        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            args = dict(zip(arg_names, args))
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(arg_names, args_grad))
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        req = _norm_grad_req(grad_req, arg_names)
        args_grad = args_grad or {}
        aux_states = aux_states or {}
        return Executor(self, ctx, dict(args or {}), dict(args_grad), req,
                        dict(aux_states), group2ctx=group2ctx)

    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx, args=kwargs, grad_req="null")
        return ex.forward()

    # gradient: reference Symbol.gradient is rarely used directly; the
    # Executor's backward covers training.  Provided for API parity.
    def simple_eval(self, ctx=None, **kwargs):
        return self.eval(ctx, **kwargs)


def _abstract_node(node: _Node, attrs, in_shapes):
    """Output shapes of one node via jax.eval_shape (FInferShape analog)."""
    import jax

    op = node.op
    if op.train_aware:
        attrs = AttrDict({**attrs, "__train__": False})
    avals = [jax.ShapeDtypeStruct(s, np.float32) for s in in_shapes]
    if op.needs_rng:
        avals = [jax.ShapeDtypeStruct((2,), np.uint32)] + avals
    from .. import telemetry
    with telemetry.paused():    # a shape asked about is no compiled variant
        out = jax.eval_shape(lambda *xs: op.fn(attrs, *xs), *avals)
    if not isinstance(out, (tuple, list)):
        out = (out,)
    return [tuple(o.shape) for o in out]


def _norm_grad_req(grad_req, arg_names):
    if isinstance(grad_req, str):
        return {n: grad_req for n in arg_names}
    if isinstance(grad_req, (list, tuple)):
        return dict(zip(arg_names, grad_req))
    out = {n: "null" for n in arg_names}
    out.update(grad_req or {})
    return out


# --------------------------------------------------------------------------
# symbol creation
# --------------------------------------------------------------------------
def _without_bias(attrs, names):
    return names[:-1] if attrs.get("no_bias") else names


_LATENT_INPUTS = ("q_a_weight", "q_a_norm_gamma", "q_b_weight",
                  "kv_a_weight", "kv_a_norm_gamma", "kv_b_weight")


def _attention_inputs(attrs, names):
    """MultiHeadAttention: the per-head gains exist only under qk_norm;
    latent attention (kv_lora_rank) takes its own six matrices and gains in
    place of query / key / value, then out_proj_weight; without a query
    rank (q_lora_rank 0) one query_weight in place of the query's three."""
    if attrs.get("kv_lora_rank"):
        query = _LATENT_INPUTS[:3] if attrs.get("q_lora_rank") \
            else ("query_weight",)
        return ["data", *query, *_LATENT_INPUTS[3:], "out_proj_weight"]
    plain = [n for n in names if n not in _LATENT_INPUTS]
    return plain if attrs.get("qk_norm") else plain[:-2]


#: ops whose attrs switch declared inputs off: op -> (attrs, names) -> names
_SWITCHED_ARGS = {
    "Convolution": _without_bias, "Deconvolution": _without_bias,
    "FullyConnected": _without_bias, "AttentionConvolution": _without_bias,
    # gamma exists only for the prelu variant
    "LeakyReLU": lambda attrs, names: names if attrs.get(
        "act_type", "leaky") == "prelu" else names[:-1],
    "MultiHeadAttention": _attention_inputs,
    # softmax scores are selected as they are: no selection bias to carry
    "SparseMoE": lambda attrs, names: names if attrs.get(
        "score") != "softmax" else [n for n in names if n != "expert_bias"],
}


def _arg_names(op, kwargs) -> List[str]:
    """The inputs a node of ``op`` with these attrs takes, by name and in
    order: the op's declared arguments without those its attrs switch off
    (reference behavior: ``no_bias`` drops ``bias``)."""
    names = list(op.arg_names or ())
    switch = _SWITCHED_ARGS.get(op.name)
    if switch is None or not names:
        return names
    return switch(op.parse_attrs({k: v for k, v in kwargs.items()
                                  if v is not None}), names)


def _create(op_name: str, sym_inputs: Sequence[Symbol],
            kwargs: Dict[str, Any], name: Optional[str] = None,
            attr: Optional[Dict[str, str]] = None) -> Symbol:
    op = get_op(op_name)
    kwargs = {k: v for k, v in kwargs.items() if v is not None}
    name = name or kwargs.pop("name", None)
    kwargs.pop("name", None)
    # explicit names also route through the scope manager so a Prefix scope
    # (gluon name_scope) prepends its prefix (reference _ctypes/symbol.py)
    from ..name import current_scope as _cs
    name = _cs().get(name, op.name.lower())

    arg_names = _arg_names(op, kwargs)
    entries: List[Tuple[Optional[_Node], int]] = []
    for s in sym_inputs:
        if s is None:
            # interior gap from keyword placement: auto-create a variable
            # named after the (scope-resolved) node name + arg name
            argname = arg_names[len(entries)] \
                if len(entries) < len(arg_names) else "arg%d" % len(entries)
            entries.append((_Node(None, "%s_%s" % (name, argname), {}, []), 0))
            continue
        if len(s._outputs) != 1:
            raise MXNetError("op inputs must be single-output symbols")
        entries.append(s._outputs[0])

    # auto-create missing parameter variables (reference behavior: calling
    # sym.Convolution(data=x, name='c1') creates c1_weight / c1_bias)
    while len(entries) < len(arg_names):
        v = _Node(None, "%s_%s" % (name, arg_names[len(entries)]), {}, [])
        entries.append((v, 0))

    # AttrScope defaults (ctx_group, __lr_mult__, ...) apply to EVERY node
    # created in scope — including operator-overload nodes (a * b) that
    # don't route through the generated functions (reference: AttrScope
    # applied in symbol creation C API).  Precedence: op kwargs > explicit
    # attr dict > scope defaults.
    from ..attribute import current_attrs
    attrs = current_attrs()
    if attr:
        attrs.update(attr)
    attrs.update(kwargs)
    node = _Node(op, name, attrs, entries)
    nvis = node.num_visible()
    return Symbol([(node, i) for i in range(nvis)])


def Variable(name: str, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, stype=None, **kwargs) -> Symbol:
    # scope defaults apply to variables too (reference AttrScope behavior:
    # a var created in AttrScope(__lr_mult__=...) carries the attr)
    from ..attribute import current_attrs
    attrs = current_attrs()
    attrs.update(attr or {})
    if shape is not None:
        attrs["__shape__"] = tuple(shape)
    if dtype is not None:
        attrs["__dtype__"] = np.dtype(dtype).name
    if lr_mult is not None:
        attrs["__lr_mult__"] = lr_mult
    if wd_mult is not None:
        attrs["__wd_mult__"] = wd_mult
    if init is not None:
        # store full init spec (class + kwargs) as the reference does
        # (symbol.py:2484-2486 stores init.dumps() JSON)
        attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    attrs.update(kwargs)
    return Symbol([(_Node(None, name, attrs, []), 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    outs = []
    for s in symbols:
        outs.extend(s._outputs)
    return Symbol(outs)


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


def load_json(json_str: str) -> Symbol:
    """Load reference-format graph JSON (both 'attrs' and legacy 'param'
    keys accepted — the legacy_json_util.cc upgrade path)."""
    g = json.loads(json_str)
    nodes_js = g["nodes"]
    built: List[_Node] = []
    for nj in nodes_js:
        # 'attrs' (1.x), 'attr' (0.x-era), 'param' (pre-NNVM) — the
        # legacy_json_util.cc upgrade chain collapsed into one lookup
        attrs = dict(nj.get("attrs") or nj.get("attr")
                     or nj.get("param") or {})
        inputs = [(built[int(e[0])], int(e[1])) for e in nj.get("inputs", [])]
        if nj["op"] == "null":
            built.append(_Node(None, nj["name"], attrs, []))
        else:
            built.append(_Node(get_op(nj["op"]), nj["name"], attrs, inputs))
    heads = g.get("heads") or [[len(built) - 1, 0, 0]]
    return Symbol([(built[int(h[0])], int(h[1])) for h in heads])


# convenience creators mirroring mx.sym.zeros/ones
def zeros(shape, dtype="float32", name=None):
    return _create("_zeros", [], {"shape": shape, "dtype": dtype}, name)


def ones(shape, dtype="float32", name=None):
    return _create("_ones", [], {"shape": shape, "dtype": dtype}, name)


def arange(start, stop=None, step=1.0, name=None, dtype="float32"):
    return _create("_arange", [], {"start": start, "stop": stop,
                                   "step": step, "dtype": dtype}, name)
