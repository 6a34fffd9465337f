"""Dependent job graphs, statically verified before dispatch.

Builds the two canonical graph shapes (a K=8 self-scaling chain and a
diamond whose arms run on disjoint cluster windows), runs them through
``verify_graph`` — zero diagnostics — then submits them and shows the
scoreboarded out-of-order dispatch path: 0 intermediate d2h bytes, one
device-to-device forward per edge.  Finally it seeds a defect (a
dependency cycle) and shows the submit gate rejecting it *before* any
staging, with a stable ``OFL001`` diagnostic.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_ENABLE_X64=true PYTHONPATH=src python examples/job_graph.py

The graph builders are imported by ``make verify-graphs`` (the
zero-diagnostics gate over every checked-in graph), so they construct
nodes without touching devices.
"""

import numpy as np

from repro.api import GraphNode, Ref, Session, VerificationError, verify_graph
from repro.core import jobs

CHAIN_K = 8
N = 2048


def build_chain(K: int = CHAIN_K):
    """y ← 2.5·x + y repeated K times, each link reading the previous
    node's result through a d2d-forwarded ``Ref``."""
    job = jobs.make_axpy(N)
    ops, _ = job.make_instance(0)
    ops = {k: np.asarray(v, dtype=np.float64) for k, v in ops.items()}
    nodes = [GraphNode(job, ops, name="n0")]
    for k in range(1, K):
        nodes.append(GraphNode(job, {"x": ops["x"], "y": Ref(f"n{k-1}")},
                               name=f"n{k}"))
    return nodes


def build_diamond():
    """src fans out to two half-mesh arms that rejoin."""
    job = jobs.make_axpy(N)
    ops, _ = job.make_instance(1)
    ops = {k: np.asarray(v, dtype=np.float64) for k, v in ops.items()}
    return [
        GraphNode(job, ops, name="src"),
        GraphNode(job, {"x": ops["x"], "y": Ref("src")}, name="l",
                  clusters=[0, 1, 2, 3]),
        GraphNode(job, {"x": ops["x"], "y": Ref("src")}, name="r",
                  clusters=[4, 5, 6, 7]),
        GraphNode(job, {"x": Ref("l"), "y": Ref("r")}, name="join"),
    ]


def build_reshard():
    """A serial wide -> narrow -> wide chain: both edges pay a d2d
    reshard forward on the critical path.  Deliberate — it is the perf
    linter's worked example (section 5 of ``main``), so the file-level
    allow below keeps its ``OFLP104`` findings out of ``make
    lint-graphs`` while ``python -m repro.lint`` still reports them."""
    # repro: allow(OFLP104) -- intentional reshard, demonstrated in main()
    job = jobs.make_axpy(N)
    ops, _ = job.make_instance(3)
    ops = {k: np.asarray(v, dtype=np.float64) for k, v in ops.items()}
    return [
        GraphNode(job, ops, name="wide"),
        GraphNode(job, {"x": ops["x"], "y": Ref("wide")}, name="narrow",
                  clusters=[0, 1, 2, 3]),
        GraphNode(job, {"x": ops["x"], "y": Ref("narrow")}, name="tail"),
    ]


def build_graphs():
    """name -> GraphNode list, for the ``make verify-graphs`` gate."""
    return {"chain": build_chain(), "diamond": build_diamond(),
            "reshard": build_reshard()}


def main() -> None:
    print("=== 1. static verification: both graphs come back clean ===")
    for name, nodes in build_graphs().items():
        diags = verify_graph(nodes, default_width=8)
        print(f"  {name}: {len(nodes)} nodes -> {len(diags)} diagnostics")
        assert not diags

    print("\n=== 2. the chain: forwarded results, 0 intermediate d2h ===")
    sess = Session()
    nodes = build_chain()
    out = sess.submit_graph(nodes).wait()
    final = out[f"n{CHAIN_K - 1}"]
    print(f"  forwards={sess.stats.forwards} (one per edge), "
          f"intermediate d2h bytes="
          f"{sess.stats.d2h_bytes - final.nbytes}")

    seq = np.asarray(nodes[0].operands["y"], dtype=np.float64)
    x = np.asarray(nodes[0].operands["x"], dtype=np.float64)
    for _ in range(CHAIN_K):
        seq = 2.5 * x + seq
    print(f"  allclose vs sequential numpy: "
          f"{np.allclose(np.asarray(final), seq)}")

    print("\n=== 3. the diamond: both arms in flight concurrently ===")
    gh = sess.submit_graph(build_diamond())
    gh.wait()
    print(f"  max_inflight={gh.max_inflight} (>= 2: arms overlapped)")

    print("\n=== 4. perf lint: the reshard chain leaves cycles on "
          "the table ===")
    from repro.analysis import perflint
    nodes = build_reshard()
    findings = perflint.lint_graph(nodes, default_width=8)
    for f in findings:
        print(f"  {f}")
    fixed = perflint.apply(findings, nodes=nodes).nodes
    out_a = sess.submit_graph(nodes).wait()
    out_b = sess.submit_graph(fixed).wait()
    same = all(np.array_equal(np.asarray(out_a[k]), np.asarray(out_b[k]))
               for k in out_a)
    print(f"  autofixed graph bit-identical: {same}")

    print("\n=== 5. a seeded defect is rejected before any staging ===")
    job = jobs.make_axpy(N)
    ops, _ = job.make_instance(2)
    bad = [GraphNode(job, {"x": ops["x"], "y": Ref("b")}, name="a"),
           GraphNode(job, {"x": ops["x"], "y": Ref("a")}, name="b")]
    try:
        sess.submit_graph(bad)
    except VerificationError as e:
        print(f"  codes={e.codes}")
        for d in e.diagnostics:
            print(f"  {d}")


if __name__ == "__main__":
    main()
