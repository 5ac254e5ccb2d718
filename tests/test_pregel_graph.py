"""The resident Pregel graph (ISSUE 37): bagel.PregelGraph keeps a graph
loaded across runs, a run is one job of the scheduler whose programs
outlive it, every launch and read is counted and under a span, and a
device failure says so before the host loop answers.  The reference is
the benchmark's own (perf/jobs/graphalytics_pagerank.py: Graphalytics' PR
in numpy float64 over seeded Graph500 graphs).  Device runs are on sliced
meshes of one and four virtual devices."""

import numpy as np
import pytest

from dpark_tpu import DparkContext, trace
from dpark_tpu.bagel import PregelGraph, run_pregel
from dpark_tpu.backend.tpu import bagel as device_bagel

from perf.lib import manifest

DAMPING, ITERATIONS = 0.85, 10


@pytest.fixture(scope="module")
def job():
    return manifest.load_module(
        manifest.job_module_path("graphalytics_pagerank"))


@pytest.fixture(autouse=True)
def _plane_off():
    trace.configure("off")
    yield
    trace.configure("off")


@pytest.fixture(params=["tpu:1", "tpu:4"])
def tctx(request):
    c = DparkContext(request.param)
    c.start()
    yield c
    c.stop()


@pytest.fixture()
def tctx1():
    c = DparkContext("tpu:1")
    c.start()
    yield c
    c.stop()


def _inputs(job, graph):
    """What a PageRank run over `graph` starts from: the arcs, float32
    ranks, the dangling flags, the job's three functions."""
    src, dst = job.arcs(graph)
    n = len(graph["ids"])
    out = np.bincount(np.searchsorted(graph["ids"], src), minlength=n)
    return ((src, dst), (np.full(n, 1.0 / n, np.float32), out == 0),
            job.pagerank_functions(DAMPING, ITERATIONS))


def _reference(job, graph):
    return {"ids": graph["ids"], "epsilon": 1e-4,
            "ranks": job.reference_pagerank(graph, DAMPING, ITERATIONS)}


@pytest.mark.parametrize("scale", [8, 9, 10])
def test_resident_runs_equal_run_pregel_and_the_reference(tctx, job,
                                                          scale):
    graph = job.graph500(41 + scale, 0, scale)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    want = _reference(job, graph)
    resident = PregelGraph(tctx, graph["ids"], edges)
    outs = [resident.run(values, compute, send, aggregator=aggregator)
            for _ in range(2)]
    outs += [run_pregel(tctx, graph["ids"], values, edges, compute, send,
                        aggregator=aggregator) for _ in range(2)]
    assert tctx.scheduler._pregel_device_used is True
    for ids, (ranks, dangling), active in outs:
        assert ranks.dtype == np.float32 and not active.any()
        assert not dangling.any()
        assert job.verdict((ids, ranks), want, "ranks")
        assert np.array_equal(ranks, outs[0][1][0])
    errors = job.rank_errors((outs[0][0], outs[0][1][0]), want)
    assert errors[0] < 1e-5 and errors[1] < 1e-5
    kinds = [st["kind"] for rec in tctx.scheduler.history
             for st in rec["stage_info"]]
    assert kinds == ["array+pregel"] * 4
    assert not tctx.scheduler.fallback_reasons()


def test_a_dangling_vertex_gives_its_mass_to_all(tctx, job):
    """A directed graph with dangling vertices: the aggregator's term."""
    ids = np.arange(6, dtype=np.int64) * 3 + 1
    src = ids[[0, 0, 1, 2, 3]]
    dst = ids[[1, 2, 2, 0, 0]]          # vertices 4 and 5 dangle
    n = len(ids)
    rank = np.full(n, 1.0 / n)
    out = np.bincount(np.searchsorted(ids, src), minlength=n)
    for _ in range(ITERATIONS):
        msg = np.bincount(np.searchsorted(ids, dst), minlength=n,
                          weights=(rank / np.maximum(out, 1))[
                              np.searchsorted(ids, src)])
        rank = (1 - DAMPING) / n + DAMPING * (
            msg + rank[out == 0].sum() / n)
    compute, send, aggregator = job.pagerank_functions(DAMPING, ITERATIONS)
    got_ids, (ranks, _), _ = PregelGraph(tctx, ids, (src, dst)).run(
        (np.full(n, 1.0 / n, np.float32), out == 0), compute, send,
        aggregator=aggregator)
    assert np.array_equal(got_ids, ids)
    assert np.allclose(ranks, rank, rtol=1e-5)
    assert abs(float(ranks.sum()) - 1.0) < 1e-5


def test_a_second_run_builds_and_traces_nothing(tctx, job):
    graph = job.graph500(7, 0, 8)
    edges, values, _ = _inputs(job, graph)
    traced = []                 # compute and send run only when traced

    def compute(state, msg, has_msg, active, aggregated, superstep):
        traced.append("compute")
        return (state[0] + msg, state[1]), superstep < 3

    def send(state, edge_value, degree):
        traced.append("send")
        return state[0] / degree.astype(state[0].dtype)

    trace.configure("ring")
    ex = tctx.scheduler.executor
    resident = PregelGraph(tctx, graph["ids"], edges)
    loads = ex.pregel_graph_loads
    first = resident.run(values, compute, send)
    seen, programs = len(traced), len(ex._compiled)
    compiles = [r for r in trace.snapshot() if r["name"] == "compile"]
    assert {"pregel.step", "pregel.gen"} \
        == {r["args"]["program"] for r in compiles
            if r["args"]["program"].startswith("pregel")}
    second = resident.run(values, compute, send)
    assert len(traced) == seen and len(ex._compiled) == programs
    assert [r for r in trace.snapshot() if r["name"] == "compile"] \
        == compiles
    assert ex.pregel_graph_loads == loads
    assert np.array_equal(first[1][0], second[1][0])
    # and neither does run_pregel over a graph of the same size classes
    run_pregel(tctx, graph["ids"], values, edges, compute, send)
    assert len(traced) - seen <= 1      # the probe of send's messages
    assert len(ex._compiled) == programs
    assert ex.pregel_graph_loads == loads + 1


def test_a_default_of_compute_is_part_of_the_program(tctx1):
    ids = np.arange(16, dtype=np.int64)
    edges = (ids, (ids + 1) % 16)

    def make(step):
        def compute(value, msg, has_msg, active, aggregated, superstep,
                    _step=step):
            return value + _step, superstep < 1
        return compute

    def send(value, edge_value, degree):
        return value

    resident = PregelGraph(tctx1, ids, edges)
    ex = tctx1.scheduler.executor
    one = resident.run(np.zeros(16), make(1.0), send)[1]
    programs = len(ex._compiled)
    again = resident.run(np.zeros(16), make(1.0), send)[1]
    assert len(ex._compiled) == programs
    three = resident.run(np.zeros(16), make(3.0), send)[1]
    assert len(ex._compiled) > programs
    assert np.array_equal(one, np.full(16, 2.0))
    assert np.array_equal(again, one)
    assert np.array_equal(three, np.full(16, 6.0))
    keys = {device_bagel.fn_identity(make(s)) for s in (1.0, 1.0, 3.0)}
    assert len(keys) == 2


def test_a_run_is_one_job_with_its_id_on_every_span(tctx, job):
    graph = job.graph500(9, 0, 8)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    resident = PregelGraph(tctx, graph["ids"], edges)
    resident.run(values, compute, send, aggregator=aggregator)   # builds
    trace.configure("ring")
    ex = tctx.scheduler.executor
    before = len(tctx.scheduler.history)
    counters = (ex.pregel_supersteps, ex.pregel_messages,
                ex.pregel_graph_loads, ex.program_launches, ex.host_reads)
    resident.run(values, compute, send, aggregator=aggregator)
    records = tctx.scheduler.history[before:]
    assert len(records) == 1
    record = records[0]
    assert record["state"] == "done" and record["seconds"] >= 0
    assert record["scope"].startswith("Pregel@test_pregel_graph.py:")
    assert record["finished"] == record["parts"] == ex.ndev
    [stage] = record["stage_info"]
    arcs = 2 * len(graph["lo"])
    steps = ITERATIONS + 1
    assert stage["kind"] == "array+pregel"
    assert stage["supersteps"] == steps
    assert stage["messages"] == ITERATIONS * arcs
    assert "fallback_reason" not in stage

    spans = [r for r in trace.snapshot() if r.get("job") == record["id"]]
    by_name = {}
    for r in spans:
        by_name.setdefault(r["name"], []).append(r)
    for name in ("job.begin", "job", "stage.run", "stage.exec",
                 "job.finish", "egest"):
        assert len(by_name[name]) == 1, name
    assert by_name["stage.exec"][0]["args"]["source"] == "pregel"
    assert "preflight" not in by_name and "ingest" not in by_name
    supersteps = by_name["pregel.superstep"]
    assert [r["args"]["s"] for r in supersteps] == list(range(steps))
    assert [r["args"]["msgs"] for r in supersteps] \
        == [arcs] * ITERATIONS + [0]
    assert supersteps[-1]["args"]["active"] == 0
    # one device delivers over the load's destination order: no round
    one = ex.ndev == 1
    assert {r["args"]["rounds"] for r in supersteps[1:]} \
        == {0 if one else 1}
    assert {r["args"]["delivery"] for r in supersteps} \
        == {"static" if one else "exchange"}
    launches = [r["args"]["program"] for r in by_name["launch"]]
    assert launches.count("pregel.step") == steps
    assert launches.count("pregel.gen") == steps
    sites = [r["args"]["site"] for r in by_name["readback"]]
    assert sites.count("pregel.active") == steps
    assert sites.count("pregel.msgs") == steps
    assert sites.count("pregel.collect") == 5   # ids, counts, 2 leaves, active
    assert by_name["egest"][0]["args"]["rows"] == len(graph["ids"])
    # the tree: job > stage.run > stage.exec > pregel.superstep > launch
    inside = lambda a, b: (b["ts"] <= a["ts"] + 2e-6 and      # noqa: E731
                           a["ts"] + a["dur"] <= b["ts"] + b["dur"] + 2e-6)
    assert inside(by_name["stage.exec"][0], by_name["stage.run"][0])
    assert inside(by_name["stage.run"][0], by_name["job"][0])
    assert all(inside(r, by_name["stage.exec"][0])
               for name in ("pregel.superstep", "launch", "egest")
               for r in by_name[name])

    # the counters moved by what the loop's structure says
    exchanges = launches.count("exchange")
    assert exchanges == (0 if ex.ndev == 1 else ITERATIONS)
    # each exchange across devices probes its int64 key's range first
    assert launches.count("minmax") == exchanges \
        == sites.count("narrow.minmax") == sites.count("exchange.counts")
    moved = [b - a for a, b in zip(counters, (
        ex.pregel_supersteps, ex.pregel_messages, ex.pregel_graph_loads,
        ex.program_launches, ex.host_reads))]
    assert moved[:3] == [steps, ITERATIONS * arcs, 0]
    assert moved[3] == 2 * steps + 2 * exchanges == len(launches)
    assert moved[4] == len(sites)
    # two reads a superstep and the final state's five; on several
    # devices two reads an exchange (one device offers no exchange a
    # row, so its stage has no row accounting to read)
    assert len(sites) == 2 * steps + 5 + 2 * exchanges
    assert ex.pregel_static_supersteps == (2 * steps if one else 0)


def test_a_device_failure_says_why_and_the_host_answers(tctx1, job,
                                                        monkeypatch):
    graph = job.graph500(11, 0, 8)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    resident = PregelGraph(tctx1, graph["ids"], edges)

    def broken(self):
        raise RuntimeError("the chip fell over\nsecond line")
    monkeypatch.setattr(device_bagel.DevicePregel, "run", broken)
    ids, (ranks, _), _ = resident.run(values, compute, send,
                                      aggregator=aggregator)
    assert job.verdict((ids, ranks), _reference(job, graph), "ranks")
    assert tctx1.scheduler._pregel_device_used is False
    [stage] = tctx1.scheduler.history[-1]["stage_info"]
    assert stage["kind"] == "object"
    assert stage["fallback_reason"] \
        == "device Pregel failed: RuntimeError: the chip fell over"
    assert tctx1.scheduler.fallback_reasons() == [stage["fallback_reason"]]
    assert tctx1.scheduler.history[-1]["state"] == "done"


def test_a_dropped_graph_runs_on_the_host_and_says_so(tctx1, job):
    graph = job.graph500(12, 0, 8)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    resident = PregelGraph(tctx1, graph["ids"], edges)
    resident.drop()
    ids, (ranks, _), _ = resident.run(values, compute, send,
                                      aggregator=aggregator)
    assert job.verdict((ids, ranks), _reference(job, graph), "ranks")
    assert tctx1.scheduler.fallback_reasons() == ["the graph was dropped"]


def test_an_input_fault_is_raised_and_the_job_aborted(tctx1):
    from dpark_tpu.bagel import PregelInputError
    ids = np.arange(8, dtype=np.int64)
    resident = PregelGraph(tctx1, ids, (ids, (ids + 1) % 8))

    def compute(v, m, h, a, agg, s):
        return v, s < 1

    def send(v, e, deg):
        return v

    with pytest.raises(PregelInputError):
        resident.run(np.ones(8), compute, send, initial_messages=(
            np.array([0]), (np.ones(1), np.ones(1))))
    assert tctx1.scheduler.history[-1]["state"] == "aborted"
    assert not tctx1.scheduler.fallback_reasons()
    with pytest.raises(PregelInputError):
        resident.run(np.ones(5), compute, send)
    with pytest.raises(PregelInputError):
        PregelGraph(tctx1, np.zeros(4, np.int64), (ids[:1], ids[:1]))


def _bfs_reference(graph, job, root):
    ids = graph["ids"]
    src, dst = (np.searchsorted(ids, a) for a in job.arcs(graph))
    depth = np.full(len(ids), -1, np.int64)
    depth[root] = 0
    frontier, level = np.array([root]), 0
    while frontier.size:
        level += 1
        reached = np.unique(dst[np.isin(src, frontier)])
        frontier = reached[depth[reached] < 0]
        depth[frontier] = level
    return depth


def _wcc_reference(graph, job):
    ids = graph["ids"]
    src, dst = (np.searchsorted(ids, a) for a in job.arcs(graph))
    label = ids.copy()
    while True:
        nxt = label.copy()
        np.minimum.at(nxt, dst, label[src])
        if np.array_equal(nxt, label):
            return label
        label = nxt


FAR = 1 << 40


def _bfs_functions():
    import jax.numpy as jnp

    def compute(depth, msg, has_msg, active, aggregated, superstep):
        nearer = jnp.minimum(depth, msg)
        return nearer, (nearer < depth) | ((superstep == 0) & (depth == 0))

    def send(depth, edge_value, degree):
        return depth + 1
    return compute, send


def _wcc_functions():
    import jax.numpy as jnp

    def compute(label, msg, has_msg, active, aggregated, superstep):
        least = jnp.minimum(label, msg)
        return least, (least < label) | (superstep == 0)

    def send(label, edge_value, degree):
        return label
    return compute, send


def test_bfs_and_wcc_over_one_resident_graph(tctx, job):
    """Graphalytics' BFS and WCC with the `min` combine: the graph object
    is not PageRank-shaped, and its frontier-sparse supersteps halt."""
    graph = job.graph500(5, 1, 9)
    ids = graph["ids"]
    resident = PregelGraph(tctx, ids, job.arcs(graph))
    host = PregelGraph(DparkContext("local"), ids, job.arcs(graph))
    root = int(np.argmax(np.bincount(
        np.searchsorted(ids, job.arcs(graph)[0]))))
    depth0 = np.full(len(ids), FAR, np.int64)
    depth0[root] = 0
    want = _bfs_reference(graph, job, root)
    for where in (resident, host):
        got_ids, depth, active = where.run(depth0, *_bfs_functions(),
                                           combine="min")
        assert np.array_equal(got_ids, ids) and not active.any()
        assert np.array_equal(np.where(depth >= FAR, -1, depth), want)
    assert want.max() >= 2 and (want >= 0).sum() > len(ids) // 2
    labels = _wcc_reference(graph, job)
    for where in (resident, host):
        _, label, _ = where.run(ids.copy(), *_wcc_functions(),
                                combine="min")
        assert np.array_equal(label, labels)
    assert len(np.unique(labels)) >= 1
    kinds = [st["kind"] for rec in tctx.scheduler.history
             for st in rec["stage_info"]]
    assert kinds == ["array+pregel"] * 2
    steps = [st["supersteps"] for rec in tctx.scheduler.history
             for st in rec["stage_info"]]
    assert steps[0] == want.max() + 2      # a level a superstep, then halt


def test_the_host_graph_object_on_the_local_master(ctx, job):
    graph = job.graph500(3, 0, 8)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    resident = PregelGraph(ctx, graph["ids"], edges)
    want = _reference(job, graph)
    for _ in range(2):
        ids, (ranks, _), _ = resident.run(values, compute, send,
                                          aggregator=aggregator)
        assert job.verdict((ids, ranks), want, "ranks")
    assert not ctx.scheduler.history       # no job where there is no device
    resident.drop()
    assert resident.run(values, compute, send,
                        aggregator=aggregator)[0].size == len(graph["ids"])


# ----------------------------------------------------------------------
# ISSUE 38: on one device the arcs lie in their destination's order and a
# superstep combines over that fixed layout.  Every case runs the host
# loop (the golden model), a one-device mesh (the static path) and a
# two-device mesh (the exchange), and all three agree.
# ----------------------------------------------------------------------
def _random_graph(seed, n=37, ne=150):
    rng = np.random.RandomState(seed)
    ids = np.sort(rng.choice(5000, n, replace=False)).astype(np.int64)
    return rng, ids, ids[rng.randint(0, n, ne)], ids[rng.randint(0, n, ne)]


def _case_add_float32_with_a_dangling_vertex(job):
    rng, ids, src, dst = _random_graph(1)
    keep = src != ids[3]                      # vertex 3 dangles
    src, dst = src[keep], dst[keep]
    n = len(ids)
    out = np.bincount(np.searchsorted(ids, src), minlength=n)
    compute, send, aggregator = job.pagerank_functions(DAMPING, 6)
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=(np.full(n, 1.0 / n, np.float32), out == 0),
                aggregator=aggregator, rtol=1e-5)


def _case_min_with_a_sparse_frontier(job):
    graph = job.graph500(5, 1, 8)
    ids = graph["ids"]
    depth0 = np.full(len(ids), FAR, np.int64)
    depth0[len(ids) // 2] = 0
    compute, send = _bfs_functions()
    return dict(ids=ids, edges=job.arcs(graph), values=depth0,
                compute=compute, send=send, combine="min")


def _case_max(job):
    import jax.numpy as jnp
    rng, ids, src, dst = _random_graph(2)

    def compute(label, msg, has_msg, active, aggregated, superstep):
        most = jnp.maximum(label, msg)
        return most, (most > label) | (superstep == 0)

    def send(label, edge_value, degree):
        return label
    return dict(ids=ids, edges=(src, dst), values=ids.copy(),
                compute=compute, send=send, combine="max")


def _case_mul(job):
    rng, ids, src, dst = _random_graph(3)

    def compute(value, msg, has_msg, active, aggregated, superstep):
        return value * msg, superstep < 2

    def send(value, edge_value, degree):
        return value * 0 + 1.5
    return dict(ids=ids, edges=(src, dst), combine="mul",
                values=rng.rand(len(ids)) + 0.5,
                compute=compute, send=send, rtol=1e-12)


def _case_a_masked_sender(job):
    rng, ids, src, dst = _random_graph(4)

    def compute(value, msg, has_msg, active, aggregated, superstep):
        return value + msg, active & (superstep < 3)

    def send(value, edge_value, degree):
        return value + degree
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=rng.randint(0, 9, len(ids)).astype(np.int64),
                active=rng.rand(len(ids)) < 0.5)


def _case_a_send_gate_leaf(job):
    rng, ids, src, dst = _random_graph(5)

    def compute(state, msg, has_msg, active, aggregated, superstep):
        total = state[0] + msg
        # an even total sends though its vertex halts; an odd one is
        # active and sends nothing
        return (total, (total % 2 == 0) & (superstep < 3)), \
            (total % 2 == 1) & (superstep < 3)

    def send(state, edge_value, degree):
        return state[0] % 7 + 1
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=(rng.randint(0, 9, len(ids)).astype(np.int64),
                        np.zeros(len(ids), bool)),
                send_gate_leaf=1)


def _case_an_edge_value(job):
    import jax.numpy as jnp
    rng, ids, src, dst = _random_graph(6)
    dist0 = np.full(len(ids), np.inf)
    dist0[0] = 0.0

    def compute(dist, msg, has_msg, active, aggregated, superstep):
        nearer = jnp.minimum(dist, msg)
        return nearer, (nearer < dist) | ((superstep == 0) & (dist == 0))

    def send(dist, weight, degree):
        return dist + weight[0] * weight[1]
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=dist0, combine="min",
                edge_values=(rng.randint(1, 9, src.size).astype(np.float64),
                             rng.randint(1, 3, src.size).astype(np.int64)))


def _case_a_vector_message_leaf(job):
    rng, ids, src, dst = _random_graph(7)

    def compute(state, msg, has_msg, active, aggregated, superstep):
        vec, seen = msg
        return (state[0] * 0.5 + vec, state[1] + seen), superstep < 3

    def send(state, edge_value, degree):
        return state[0] / degree[..., None], state[1] * 0 + 1
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=(rng.rand(len(ids), 3),
                        np.zeros(len(ids), np.int64)), rtol=1e-12)


def _case_a_vertex_unreached_and_an_arc_to_no_vertex(job):
    rng, ids, src, dst = _random_graph(8)
    dst = np.where(dst == ids[0], ids[1], dst)     # nothing reaches 0
    dst[::5] = 7777                                # and 30 reach nobody
    assert 7777 not in ids

    def compute(value, msg, has_msg, active, aggregated, superstep):
        return value + msg * has_msg, superstep < 2

    def send(value, edge_value, degree):
        return value * 0 + 1
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=np.zeros(len(ids), np.int64),
                messages=2 * src.size)


def _arcs_case(ne):
    def case(job):
        ids = np.arange(8, dtype=np.int64) * 5 + 2
        src = ids[np.arange(ne) % 8]
        dst = ids[(np.arange(ne) * 3 + 1) % 8]

        def compute(value, msg, has_msg, active, aggregated, superstep):
            return value + msg, superstep < 3

        def send(value, edge_value, degree):
            return value + 1
        return dict(ids=ids, edges=(src, dst), compute=compute,
                    send=send, values=np.arange(8, dtype=np.int64),
                    cap_e=16 if ne == 16 else 32)
    return case


def _case_initial_messages(job):
    import jax.numpy as jnp
    rng, ids, src, dst = _random_graph(9)

    def compute(dist, msg, has_msg, active, aggregated, superstep):
        nearer = jnp.minimum(dist, msg)
        return nearer, nearer < dist

    def send(dist, edge_value, degree):
        return dist + 1
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=np.full(len(ids), FAR, np.int64), combine="min",
                initial_messages=(np.array([ids[2], ids[9], 4242]),
                                  np.array([0, 3, 0], np.int64)))


def _case_a_static_superstep(job):
    rng, ids, src, dst = _random_graph(10)

    def compute(value, msg, has_msg, active, aggregated, superstep):
        if superstep % 2:                  # a Python int, not a tracer
            return value + msg, superstep < 4
        return value * 2, True

    def send(value, edge_value, degree):
        return value
    return dict(ids=ids, edges=(src, dst), compute=compute, send=send,
                values=np.ones(len(ids), np.int64), static_superstep=True)


STATIC_CASES = {
    "add_float32_dangling": _case_add_float32_with_a_dangling_vertex,
    "min_sparse_frontier": _case_min_with_a_sparse_frontier,
    "max": _case_max, "mul": _case_mul,
    "masked_sender": _case_a_masked_sender,
    "send_gate_leaf": _case_a_send_gate_leaf,
    "edge_value": _case_an_edge_value,
    "vector_message_leaf": _case_a_vector_message_leaf,
    "unreached_vertex_and_arc_to_no_vertex":
        _case_a_vertex_unreached_and_an_arc_to_no_vertex,
    "arcs_fill_cap_e": _arcs_case(16), "arcs_padded": _arcs_case(17),
    "initial_messages": _case_initial_messages,
    "static_superstep": _case_a_static_superstep,
}


@pytest.mark.parametrize("name", sorted(STATIC_CASES))
def test_the_static_path_equals_the_host_loop_and_the_exchange(job, name):
    from dpark_tpu.bagel import _HostGraph, as_leaves
    case = STATIC_CASES[name](job)
    ids, edges = case["ids"], case["edges"]
    run = {k: case[k] for k in (
        "combine", "active", "initial_messages", "aggregator",
        "send_gate_leaf", "static_superstep") if k in case}
    want = _HostGraph(ids, edges, case.get("edge_values")).run(
        case["values"], case["compute"], case["send"],
        case.get("combine", "add"), case.get("active"),
        case.get("initial_messages"), case.get("aggregator"), 80,
        case.get("send_gate_leaf"))
    notes = {}
    for master in ("tpu:1", "tpu:2"):
        c = DparkContext(master)
        c.start()
        try:
            ex = c.scheduler.executor
            resident = PregelGraph(c, ids, edges, case.get("edge_values"))
            assert resident._device.dst_ordered is (master == "tpu:1")
            if "cap_e" in case and master == "tpu:1":
                assert resident._device.cap_e == case["cap_e"]
            got = resident.run(case["values"], case["compute"],
                               case["send"], **run)
            assert c.scheduler._pregel_device_used is True
            assert not c.scheduler.fallback_reasons()
            [note] = c.scheduler.history[-1]["stage_info"]
            notes[master] = (note["supersteps"], note["messages"])
            assert ex.pregel_static_supersteps == (
                note["supersteps"] if master == "tpu:1" else 0)
        finally:
            c.stop()
        assert np.array_equal(got[0], want[0]), master
        assert np.array_equal(got[2], want[2]), master
        for g, w in zip(as_leaves(got[1])[0], as_leaves(want[1])[0]):
            assert g.dtype == w.dtype and g.shape == w.shape
            if "rtol" in case:
                assert np.allclose(g, w, rtol=case["rtol"], atol=0), master
            else:
                assert np.array_equal(g, w), master
    assert notes["tpu:1"] == notes["tpu:2"]
    assert notes["tpu:1"][0] > 1
    if "messages" in case:         # an arc to nobody is counted as sent
        assert notes["tpu:1"][1] == case["messages"]


def test_initial_messages_arrive_bucketized_and_the_rest_delivered(
        tctx1, job):
    case = _case_initial_messages(job)
    trace.configure("ring")
    PregelGraph(tctx1, case["ids"], case["edges"]).run(
        case["values"], case["compute"], case["send"], combine="min",
        initial_messages=case["initial_messages"])
    steps = [r["args"] for r in trace.snapshot()
             if r["name"] == "pregel.superstep"]
    assert len(steps) > 2
    assert [a["rounds"] for a in steps] == [1] + [0] * (len(steps) - 1)
    assert {a["delivery"] for a in steps} == {"static"}
    step_forms = {k[3] for k in tctx1.scheduler.executor._compiled._d
                  if k[0] == "pregel.step"}
    assert step_forms == {1, "delivered"}


def test_the_one_device_programs_neither_sort_nor_loop(tctx1, job):
    graph = job.graph500(13, 0, 8)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    g = PregelGraph(tctx1, graph["ids"], edges)._device
    run = device_bagel.DevicePregel(g, values, compute, send,
                                    aggregator=aggregator)
    gen = run._p_gen_static().lower(
        g.vcnt, run.active, g.v_last, g.e_start, g.e_slot, g.e_deg,
        g.ecnt, *run.values).as_text()
    msg = g.put(np.zeros((1, g.cap_v), np.float32))
    step = run._p_step(0, 0, delivered=True).lower(
        g.put(np.zeros(1, np.int32)), g.vcnt, g.vid, run.active,
        *run.values, msg, run.active).as_text()
    import re
    loops = re.compile(r"\b(sort|while)\b")
    for text in (gen, step):
        assert not loops.search(text)
    # the senders' rows in ONE gather (flag, rank and dangling), then
    # the read at v_last of the message leaf and of the flag
    assert gen.count('"stablehlo.gather"(') == 3 and "gather" not in step
    # what the exchange's gen program holds, for the contrast
    assert loops.search(run._p_gen().lower(
        g.vcnt, run.active, g.e_dst, g.e_slot, g.e_deg, g.ecnt,
        *run.values).as_text())


def test_static_supersteps_are_counted_where_one_device_delivers(tctx,
                                                                 job):
    graph = job.graph500(14, 0, 8)
    edges, values, (compute, send, aggregator) = _inputs(job, graph)
    ex = tctx.scheduler.executor
    resident = PregelGraph(tctx, graph["ids"], edges)
    for runs in (1, 2):
        resident.run(values, compute, send, aggregator=aggregator)
        assert ex.pregel_supersteps == runs * (ITERATIONS + 1)
        assert ex.pregel_static_supersteps == (
            ex.pregel_supersteps if ex.ndev == 1 else 0)


def test_the_load_orders_arcs_by_destination_on_one_device_only(tctx):
    rng, ids, src, dst = _random_graph(11, ne=40)
    dst[:3] = 9999                           # arcs to no vertex
    weight = rng.rand(40)
    g = PregelGraph(tctx, ids, (src, dst), weight)._device
    ndev, cap_e, cap_v = g.ndev, g.cap_e, g.cap_v
    ecnt = np.asarray(g.ecnt)
    e_dst, e_slot, vid = (np.asarray(a) for a in (g.e_dst, g.e_slot, g.vid))
    e_w = np.asarray(g.e_vals[0])
    assert int(ecnt.sum()) == 40
    got = sorted((int(vid[d, e_slot[d, i]]), int(e_dst[d, i]), e_w[d, i])
                 for d in range(ndev) for i in range(ecnt[d]))
    assert got == sorted(zip(src.tolist(), dst.tolist(), weight.tolist()))
    if ndev > 1:
        # the arcs with their source's device, in the order given
        assert g.e_start is None and g.v_last is None
        from dpark_tpu.utils.phash import phash_np
        edev = phash_np(src) % np.uint32(ndev)
        for d in range(ndev):
            assert np.array_equal(e_dst[d, :ecnt[d]], dst[edev == d])
        return
    d_sorted = e_dst[0, :40]
    assert np.array_equal(d_sorted, np.sort(dst))
    order = np.argsort(dst, kind="stable")       # stable: as given
    assert np.array_equal(e_w[0, :40], weight[order])
    e_start, v_last = np.asarray(g.e_start)[0], np.asarray(g.v_last)[0]
    assert e_start.shape == (cap_e,) and v_last.shape == (cap_v,)
    assert e_start[0] and e_start[40] and not e_start[41:].any()
    assert np.array_equal(e_start[1:40], d_sorted[1:] != d_sorted[:-1])
    for slot in range(cap_v):
        at = np.flatnonzero(d_sorted == vid[0, slot])
        assert v_last[slot] == (at[-1] if at.size and slot < len(ids)
                                else -1)
    assert (v_last >= 0).sum() == len(np.intersect1d(ids, dst))
