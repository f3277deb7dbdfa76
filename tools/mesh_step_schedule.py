"""Compile a four-chip cell's mesh step program for a described ``v5e:2x2``
(no chip needed) and say where its exchange sits in the schedule.

Hand-run, on the CPU::

    python tools/mesh_step_schedule.py --workload gpt2m_train_dp4 \\
        [--layers 6] [--row] [--hlo /root/scratch/step.hlo.txt]

Binds the cell's Module on one CPU context for its shapes, builds
``Executor.step_program`` with the layout ``parallel.mesh.state_sharding``
gives on the described mesh, lowers it with ``ShapeDtypeStruct``s and
compiles it with the TPU's compiler.  Prints the compile's time, memory and
serialized size, the entry computation's instructions by kind in schedule
order (run lengths), and of the collective permutes' bytes the share that
has a backward product between start and done, and the share begun behind
the last backward product.  ``--row`` builds the program CPU meshes get (the
partitioner's blocking ``all-reduce-scatter`` fusions).  Nothing here is a
chip run: it says where ops stand, not how long they take.

Kinds: ``p`` / ``P`` a forward / backward product (a fusion that holds a
convolution), ``k`` / ``K`` a Mosaic kernel, ``u`` an update fusion, ``G`` a
blocking all-gather, ``gs`` / ``gd`` an asynchronous gather's start / done,
``S`` / ``D`` a collective permute's start / done, ``RS`` a blocking
``all-reduce-scatter`` fusion, ``A2A`` an all-to-all.
"""
import argparse
import collections
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_step(workload, layers, row):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MXNET_TPU_BF16"] = "1"
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import serialize_executable, topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import mesh as pmesh
    from perf import harness

    if row:
        real = pmesh.exchange_path
        pmesh.exchange_path = lambda *a: real(*a) and "row"
    cell = harness.load_cell(ROOT, workload)
    cfg, wl, builder = dict(cell.config), cell.workload, cell.builder
    if layers:
        cfg["n_layer"] = layers
    data_shapes, label_shapes = builder.shapes(cfg, wl)
    mod = mx.mod.Module(builder.symbol(cfg, wl),
                        data_names=tuple(data_shapes),
                        label_names=tuple(label_shapes), context=[mx.cpu(0)])
    mod.bind(data_shapes=list(data_shapes.items()),
             label_shapes=list(label_shapes.items()))
    mod.params_initialized = True       # shapes only: nothing runs
    mod.init_optimizer(kvstore=None, optimizer=wl["optimizer"],
                       optimizer_params=dict(wl["optimizer_params"]))
    fs, ex, opt = mod._fused(), mod._exec_group.execs[0], mod._optimizer
    pnames = fs._pnames
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("dp",))
    repl = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding=repl):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    shapes = [ex.arg_dict[n].shape for n in pnames]
    ssh = [pmesh.state_sharding(repl, s) for s in shapes]
    mp = [opt.fused_mp(ex.arg_dict[n]) for n in pnames]
    arity = opt.fused_state_arity()
    fn = ex.step_program(
        pnames, [opt.fused_update_mp if m else opt.fused_update for m in mp],
        mesh_sig=("described",), param_shardings=[repl] * len(pnames),
        state_shardings=ssh)
    pvals = [sds(s, ex.arg_dict[n].dtype, sh)
             for n, s, sh in zip(pnames, shapes, ssh)]
    svals = [tuple(sds(s, jnp.float32, sh) for _ in range(arity + bool(m)))
             for s, sh, m in zip(shapes, ssh, mp)]
    batch = {**data_shapes, **label_shapes}
    rows = NamedSharding(mesh, P("dp"))
    others = [sds(batch[n], ex.arg_dict[n].dtype, rows)
              for n in ex.arg_names if n not in set(pnames)]
    keys = ex._keys(ex._plan(True))
    ogs = ex._ograds_for({**{n: ex.arg_dict[n].shape for n in ex.arg_names},
                          **batch})
    vec = sds((len(pnames),), jnp.float32)
    print("parameters %d, split over dp %d, exchange %s" % (
        len(pnames), sum(sh is not repl for sh in ssh),
        pmesh.exchange_path([repl] * len(pnames), ssh)))
    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = fn.lower(
            pvals, svals, others, [], sds(keys.shape, keys.dtype),
            [sds(o.shape, o.dtype) for o in ogs], vec, vec, vec,
            sds((), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    print("lowered and compiled in %.0f s; arguments %.2f GB, temporaries "
          "%.2f GB, serialized %.1f MB" % (
              time.time() - t0, mem.argument_size_in_bytes / 1e9,
              mem.temp_size_in_bytes / 1e9,
              len(serialize_executable.serialize(compiled)[0]) / 1e6))
    return compiled.as_text()


def read(hlo):
    comps = {}
    for comp in re.split(
            r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", hlo):
        head = comp.split("\n", 1)[0]
        comps["ENTRY" if head.startswith("ENTRY")
              else head.split(" ", 1)[0]] = comp
    entry = comps["ENTRY"].splitlines()
    rows = []
    for i, ln in enumerate(entry):
        found = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", ln)
        if not found:
            continue
        name, shape, op = found.groups()
        scope = re.search(r'op_name="([^"]*)"', ln)
        scope = scope.group(1) if scope else ""
        calls = re.search(r"calls=(%[\w.\-]+)", ln)
        body = comps.get(calls.group(1), "") if calls else ""
        backward = "transpose(jvp" in scope
        kind = None
        if op == "collective-permute-start":
            kind = "S"
        elif op == "collective-permute-done":
            kind = "D"
        elif op == "fusion" and "all-reduce-scatter" in ln:
            kind = "RS"
        elif op == "all-to-all":
            kind = "A2A"
        elif name.startswith("async-collective-start"):
            kind = "gs"
        elif name.startswith("async-collective-done"):
            kind = "gd"
        elif op == "all-gather":
            kind = "G"
        elif op == "custom-call" and "tpu_custom_call" in ln:
            kind = "K" if backward else "k"
        elif op == "convolution" or "convolution(" in body:
            kind = "P" if backward else "p" if "jvp(" in scope else None
        elif op == "fusion" and "Optimizer::" in scope:
            kind = "u"
        if kind:
            nbytes = sum(
                (2 if dt == "bf16" else 4)
                * math.prod(int(d) for d in dims.split(",") if d)
                for dt, dims in re.findall(r"(bf16|f32)\[([\d,]*)\]", shape))
            rows.append((i, kind, name, nbytes))
    print("entry instructions %d: %s" % (
        len(entry), dict(collections.Counter(k for _, k, *_ in rows))))
    runs = []
    for _, kind, *_ in rows:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    print(" ".join("%s%s" % (k, n if n > 1 else "") for k, n in runs))
    work = [i for i, k, *_ in rows if k in "PK"]
    begun, total, beside, late = {}, 0, 0, 0
    for i, kind, name, nbytes in rows:
        if kind == "S":
            begun[name.replace("start", "done")] = (i, nbytes // 2)
        elif kind == "D" and name in begun:
            start, nbytes = begun.pop(name)
            total += nbytes
            beside += nbytes * any(start < j < i for j in work)
            late += nbytes * (start > max(work))
    if total:
        print("collective permutes: %.0f MB a replica sends; %.0f %% with a "
              "backward product between start and done, %.0f %% begun "
              "behind the last backward product"
              % (total / 1e6, 100.0 * beside / total, 100.0 * late / total))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="gpt2m_train_dp4")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the configuration to this many layers")
    ap.add_argument("--row", action="store_true",
                    help="the program a CPU mesh gets")
    ap.add_argument("--hlo", help="write the compiled module's text here")
    args = ap.parse_args()
    hlo = compile_step(args.workload, args.layers, args.row)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    read(hlo)


if __name__ == "__main__":
    main()
