"""Compile every cell's call for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/aot.py [--topology v5e:2x2]

For each cell it lowers the program the window drives at the cell's own
shapes (the chunk step of a chunked or sharded grid, the vmapped sweep
of a monolithic call or of a stream) and compiles it for devices of the
described topology: one device for a one-chip cell, a mesh of
``devices`` for a sharded one.  It prints ``memory_analysis()`` per cell
and fails where the TPU compiler refuses.  Nothing runs.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def shapes(tree, rows, sharding):
    """ShapeDtypeStructs of a replica-leading pytree, ``rows`` replicas."""
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype,
                                       sharding=sharding), tree)


def lower_cell(res, topo):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from bench import harness as H
    from repro.launch import chunked as CH
    from repro.launch import experiment as X
    cfg, traffic = res["config"], res["traffic"]
    spec = H.make_spec(cfg, traffic, seed=0)
    n_dev = traffic["devices"]
    if n_dev > 1:
        mesh = Mesh(np.array(topo.devices[:n_dev]).reshape(n_dev, 1),
                    ("data", "model"))
        rep = NamedSharding(mesh, PS(("data", "model")))
        whole = NamedSharding(mesh, PS())
    else:
        rep = whole = jax.sharding.SingleDeviceSharding(topo.devices[0])
    one = X.normalize_chunk(spec, 0, 1)
    rows = traffic.get("chunk", traffic["replicas"])
    if spec.streaming:
        args = (X.to_streams(one, spec.stream_chunk), one.mtype,
                one.tables.eet, one.tables.power, one.policy_ids,
                one.dynamics)
    else:
        args = (one.tasks, one.mtype, one.tables, one.policy_ids,
                one.dynamics, one.parents)
    args = shapes(args, rows, rep)
    if "chunk" in traffic:
        params = spec.stream_params if spec.streaming else spec.sim_params
        keys = jax.eval_shape(X.compile_experiment(spec),
                              *jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                                  s.shape, s.dtype), args), None)
        cols = {k: CH._init_column(len(spec.policy.policies), CH.SWEEP_SPEC)
                for k in keys}
        cols = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=whole), cols)
        pol = jax.ShapeDtypeStruct((rows,), np.int32, sharding=rep)
        step = CH._compile_chunk_step(params, CH.SWEEP_SPEC, spec.streaming,
                                      True)
        return step.lower(cols, pol, args, None).compile()
    fn = X.compile_experiment(spec)
    return fn.lower(*args, None).compile()


def main(argv=None) -> int:
    import argparse

    import jax
    from jax.experimental import topologies

    from bench import harness as H
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    for cell in H.load_benchmark()["workloads"]:
        res = H.resolve(cell["name"])
        compiled = lower_cell(res, topo)
        print(cell["name"], compiled.memory_analysis(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
