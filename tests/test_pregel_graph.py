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
    assert {r["args"]["rounds"] for r in supersteps[1:]} == {1}
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
    # two reads a superstep and the final state's five; on one device
    # the stage's row accounting, on several two reads an exchange
    assert len(sites) == 2 * steps + 5 + (1 if ex.ndev == 1
                                          else 2 * exchanges)


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
