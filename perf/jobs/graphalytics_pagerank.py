"""graphalytics-pagerank: LDBC Graphalytics' PageRank (algorithm PR) over
its Graph500 data sets, timed as the source times it: the algorithm over a
graph that is ALREADY loaded (Graphalytics' `processing time`; loading is
reported apart).

A Graph500 data set is the Graph500 Kronecker (R-MAT) generator's output
(initiator A, B, C, D = 0.57, 0.19, 0.19, 0.05, edge factor 16, vertex
labels permuted) made undirected and simple, without its isolated
vertices.  The dpark job over one resident graph is
`bagel.PregelGraph(...).run(...)`: ranks as float32 vertex state, one
message an arc, the `add` combine, and the dangling mass (none in these
graphs; the job is written for any graph) and the vertex count as the
run's aggregator.  The numpy reference is the source's definition in
float64, one `np.bincount` an iteration.

Everything of this configuration: the seeded generator, the load (which
stops a program without a resident graph), the dpark calls, and the
reference (no code shared with dpark_tpu).
"""

import math

import numpy as np

INITIATOR = {"a": 0.57, "b": 0.19, "c": 0.19, "d": 0.05}
EDGE_FACTOR = 16
PROBE_SCALE = 8
# on the device: e_dst int64, e_slot int32, e_deg int64 an arc slot, the
# vertex id int64 a vertex slot; slots come in powers of two
ARC_SLOT_BYTES = 20
VERTEX_SLOT_BYTES = 8

QUERIES = {"pagerank": {"ranks": None}}


def kronecker_edges(rng, scale, initiator=None, edge_factor=EDGE_FACTOR):
    """The Graph500 generator: `edge_factor` x 2**scale (start, end)
    pairs over 2**scale vertices, a quadrant of the initiator drawn a bit
    and an edge, then the vertex labels permuted."""
    init = initiator or INITIATOR
    m = edge_factor << scale
    ab = init["a"] + init["b"]
    c_norm = init["c"] / (1.0 - ab)
    a_norm = init["a"] / ab
    start = np.zeros(m, np.int64)
    end = np.zeros(m, np.int64)
    for bit in range(scale):
        down = rng.random(m) > ab
        right = rng.random(m) > np.where(down, c_norm, a_norm)
        start |= down.astype(np.int64) << bit
        end |= right.astype(np.int64) << bit
    label = rng.permutation(1 << scale)
    return label[start], label[end]


def graph500(seed, g, scale, initiator=None, edge_factor=EDGE_FACTOR):
    """Graph `g` of `seed` as Graphalytics holds a Graph500 data set:
    undirected and simple (self-loops and duplicate edges dropped),
    isolated vertices dropped.  {"ids": the vertices' labels, sorted;
    "lo", "hi": each edge once, lo < hi, as labels}."""
    start, end = kronecker_edges(np.random.default_rng([seed, g]), scale,
                                 initiator, edge_factor)
    keep = start != end
    lo = np.minimum(start, end)[keep]
    hi = np.maximum(start, end)[keep]
    pair = np.unique((lo << scale) | hi)
    lo, hi = pair >> scale, pair & ((1 << scale) - 1)
    return {"ids": np.unique(np.concatenate([lo, hi])), "lo": lo, "hi": hi}


def arcs(graph):
    """Every edge both ways: (source labels, destination labels)."""
    return (np.concatenate([graph["lo"], graph["hi"]]),
            np.concatenate([graph["hi"], graph["lo"]]))


def _slots(n):
    return max(8, 1 << math.ceil(math.log2(max(n, 1))))


def make_data(config, traffic, seed, scale):
    """`resident_partitions` graphs of the configuration's Graph500 scale
    (a rehearsal's divisor takes its log2 off the scale, never below
    PROBE_SCALE)."""
    if config["generator"]["initiator"] != INITIATOR \
            or config["generator"]["edge_factor"] != EDGE_FACTOR:
        raise ValueError("generator %r is not implemented (known: the "
                         "Graph500 Kronecker initiator, edge factor 16)"
                         % (config["generator"],))
    full = int(config["graph500_scale"])
    if int(traffic["rows_per_job"]) != (EDGE_FACTOR + 1) << full:
        raise ValueError("rows_per_job %s is not 17 x 2**%d, the nominal "
                         "vertices and edges of the configuration's scale"
                         % (traffic["rows_per_job"], full))
    s = max(PROBE_SCALE, full - int(math.log2(max(1, scale))))
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=4) as pool:
        graphs = list(pool.map(
            lambda g: graph500(seed, g, s),
            range(int(traffic["resident_partitions"]))))
    rows = sum(len(g["ids"]) + len(g["lo"]) for g in graphs) // len(graphs)
    return {"graphs": graphs, "scale": s, "rows": rows,
            "damping": float(config["algorithm"]["damping"]),
            "iterations": int(config["algorithm"]["iterations"]),
            "epsilon": float(config["algorithm"]["epsilon"])}


def input_rows(data):
    """|V| + |E|, edges counted once as the source counts them (its
    EVPS), the mean over the resident graphs."""
    return data["rows"]


def n_partitions(data):
    return len(data["graphs"])


def resident_bytes(data):
    return sum(ARC_SLOT_BYTES * _slots(2 * len(g["lo"]))
               + VERTEX_SLOT_BYTES * _slots(len(g["ids"]))
               for g in data["graphs"])


def reference_pagerank(graph, damping, iterations):
    """Graphalytics' PR in float64 over the sorted vertices:
    PR_0(v) = 1/|V|; PR_i(v) = (1-d)/|V| + d * sum over u in N_in(v) of
    PR_{i-1}(u)/|N_out(u)| + d/|V| * sum over dangling w of PR_{i-1}(w)."""
    ids = graph["ids"]
    n = len(ids)
    src, dst = arcs(graph)
    src = np.searchsorted(ids, src)
    dst = np.searchsorted(ids, dst)
    out = np.bincount(src, minlength=n)
    dangling = out == 0
    share = 1.0 / np.maximum(out, 1)
    rank = np.full(n, 1.0 / n)
    for _ in range(iterations):
        rank = ((1.0 - damping) / n
                + damping * np.bincount(dst, weights=(rank * share)[src],
                                        minlength=n)
                + damping / n * rank[dangling].sum())
    return rank


def reference(data, part, query, action):
    if action not in QUERIES[query]:
        raise ValueError("unknown action %r" % action)
    graph = data["graphs"][part]
    return {"ids": graph["ids"], "epsilon": data["epsilon"],
            "ranks": reference_pagerank(graph, data["damping"],
                                        data["iterations"])}


def pagerank_functions(damping, iterations):
    """(compute, send, aggregator) of the PageRank run.  Vertex state is
    (rank, dangling); the aggregator hands every vertex the dangling mass
    and the vertex count of the state before the superstep.  Superstep 0
    sends the initial ranks; supersteps 1..iterations each apply one
    iteration, and the last sends nothing, so a run is iterations + 1
    supersteps.  No function captures a graph's size: the graphs of one
    capacity class share their programs.  Operators only, so that the
    same functions run over numpy on the `local` master."""
    def compute(state, msg, has_msg, active, aggregated, superstep):
        rank, dangling = state
        mass, count = aggregated
        first = superstep == 0
        nxt = (1.0 - damping) / count + damping * (msg + mass / count)
        return ((first * rank + (1 - first) * nxt, dangling),
                superstep < iterations)

    def send(state, edge_value, degree):
        return state[0] / degree.astype(state[0].dtype)

    def create(state):
        rank, dangling = state
        # the dangling vertices' ranks, and a one a vertex in the ranks'
        # own type (an integer count would take the division to float64)
        return rank * dangling, rank * 0 + 1

    return compute, send, (create, "add")


def _resident(ctx, graph):
    """One graph loaded through the program's resident-graph API, with
    what a run over it starts from."""
    from dpark_tpu.bagel import PregelGraph
    src, dst = arcs(graph)
    n = len(graph["ids"])
    out = np.bincount(np.searchsorted(graph["ids"], src), minlength=n)
    return {"graph": PregelGraph(ctx, graph["ids"], (src, dst)),
            "rank0": np.full(n, 1.0 / n, np.float32),
            "dangling": out == 0}


def _pagerank(loaded, fns):
    compute, send, aggregator = fns
    ids, (ranks, _), _ = loaded["graph"].run(
        (loaded["rank0"], loaded["dangling"]), compute, send,
        combine="add", aggregator=aggregator)
    return ids, ranks


def _probe(ctx):
    """A 2**PROBE_SCALE-vertex graph loaded through the resident-graph
    API and dropped, before anything else is: a program without the API
    ends here (an ImportError), in seconds.  It is not RUN: the TPU
    compiler takes minutes over device Pregel's programs at any size
    (PERF.md section 6 (b)), so a probe's run would double a compiling
    set-up; the first real job below is checked instead."""
    _resident(ctx, graph500(0, 0, PROBE_SCALE))["graph"].drop()


def load(ctx, data, ndev):
    """The probe, then every graph loaded once, then the timed job once
    over each (a graph of another capacity class would compile inside
    the window otherwise), each of them one job of kind array+pregel or
    the program cannot run the configuration.  The graphs are not in the
    executor's result cache, so `resident_ids` is empty:
    `pregel_graph_loads_per_job` guards residency instead."""
    _probe(ctx)
    loaded = {"graphs": [_resident(ctx, g) for g in data["graphs"]],
              "fns": pagerank_functions(data["damping"],
                                        data["iterations"]),
              "resident_ids": []}
    since = len(ctx.scheduler.history)
    for part in range(len(loaded["graphs"])):
        run(ctx, loaded, part, "pagerank", "ranks", ndev)
    kinds = [str(st.get("kind")) for rec in ctx.scheduler.history[since:]
             for st in rec["stage_info"]]
    if kinds != ["array+pregel"] * len(loaded["graphs"]) \
            or ctx.scheduler.fallback_reasons():
        raise RuntimeError(
            "a run over a resident graph was not one job of kind "
            "array+pregel (stage kinds %s, fallback %s): this program "
            "cannot run the configuration"
            % (kinds, ctx.scheduler.fallback_reasons()))
    return loaded


def run(ctx, tables, part, query, action, ndev):
    """One job: PageRank of the configuration's iterations over graph
    `part`; (ids, ranks) of every vertex as numpy columns."""
    if action not in QUERIES[query]:
        raise ValueError("unknown action %r" % action)
    return _pagerank(tables["graphs"][part], tables["fns"])


def rank_errors(result, expected):
    """(largest |got - ref| / ref over the vertices, |sum - 1|), or None
    when the vertices returned are not the graph's, each once."""
    ids, ranks = result
    if not np.array_equal(np.asarray(ids), expected["ids"]):
        return None
    got = np.asarray(ranks, np.float64)
    ref = expected["ranks"]
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return None
    return (float(np.max(np.abs(got - ref) / ref)),
            abs(float(got.sum()) - 1.0))


def verdict(result, expected, action):
    """The source's rule: every vertex once, |got - ref| <= epsilon x ref
    for every vertex; and the ranks sum to 1 within epsilon (no vertex
    dangles, so no mass leaves)."""
    errors = rank_errors(result, expected)
    return errors is not None and errors[0] <= expected["epsilon"] \
        and errors[1] <= expected["epsilon"]


def least(config, traffic, data, ndev, query):
    """HBM: an iteration reads two 4-byte slots an arc (the source's
    place and the target's) and, a vertex, reads a rank and a degree and
    writes a rank.  The cell is one chip: nothing crosses."""
    graphs = data["graphs"]
    arcs_n = 2 * sum(len(g["lo"]) for g in graphs) / len(graphs)
    vertices = sum(len(g["ids"]) for g in graphs) / len(graphs)
    return {"hbm_bytes": float(data["iterations"]
                               * (8 * arcs_n + 12 * vertices)) / ndev,
            "ici_bytes": 0.0}
