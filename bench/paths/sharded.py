"""The chunked grid with its replica axis sharded over a local mesh:
``run_experiment(spec, mesh=make_local_mesh(data=D), chunk=C)``."""
from repro.launch import experiment as X
from repro.launch.mesh import make_local_mesh


def call(spec, traffic):
    mesh = make_local_mesh(data=traffic["devices"])
    res = X.run_experiment(spec, mesh=mesh, chunk=traffic["chunk"],
                           keep_replicas=True)
    return res.metrics, res.agg


def warm(spec, traffic):
    """One call of two chunks: the second chunk's step takes the first's
    sharded aggregate, not a host array, and compiles once more."""
    call(spec.with_(n_replicas=2 * traffic["chunk"]), traffic)
