"""uservisits-q3: AMPLab Big Data Benchmark query 3C,

    SELECT sourceIP, totalRevenue, avgPageRank FROM
      (SELECT sourceIP, AVG(pageRank) AS avgPageRank,
              SUM(adRevenue) AS totalRevenue
       FROM rankings AS R, uservisits AS UV
       WHERE R.pageURL = UV.destURL
         AND UV.visitDate BETWEEN Date('1980-01-01') AND Date('2010-01-01')
       GROUP BY UV.sourceIP)
    ORDER BY totalRevenue DESC LIMIT 1

over partitions of (destURL S100, sourceIP S16, visitDate int32, adRevenue
float32) and one table of (pageURL S100, pageRank int32), all resident in
HBM.  The dpark chain is filter -> map -> join -> map -> reduceByKey ->
top(1): a big-big join on a 100-byte string, then a group-by on a 16-byte
one.  AVG(pageRank) is SUM / COUNT, divided on the host.

Everything of this configuration: seeded data, the load to HBM (which
proves that it ran on the array path), the dpark calls, and the numpy
reference (over the bytes; no code shared with dpark_tpu).  The five
functions of the chain are module-level with distinct code: fuse.fn_key
ignores __defaults__ (PERF.md, Findings).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perf.lib import manifest

URL_BYTES = 100
IP_BYTES = 16
VISIT_BYTES = URL_BYTES + IP_BYTES + 4 + 4      # + visitDate + adRevenue
PAGE_BYTES = URL_BYTES + 4                      # + pageRank
GROUP_BYTES = IP_BYTES + 8 + 8 + 4      # sourceIP, sum of ranks, count, sum
DATE_LOW, DATE_HIGH = 3652, 14610       # 1980-01-01 .. 2010-01-01, in days
SAMPLE_PREFIX = b"173."     # collect_sample keeps the groups that begin so
SUM_ULP = 2.0 ** -23        # one float32 rounding an addition (the guarantee)
PROOF_ROWS = 4096           # the slice `load` proves the path with
URL_PREFIX = b"http://"
URL_DIGITS = 6              # base-26 digits of the page number: 308M pages

DISTRIBUTIONS = {
    "page_url_distribution": {
        "kind": "distinct_lowercase", "prefix": URL_PREFIX.decode(),
        "length": {"kind": "uniform", "low": 20, "high": URL_BYTES + 1}},
    "page_rank_distribution": {
        "kind": "power_of_popularity", "low": 1, "high": 10000,
        "exponent": 0.5, "dtype": "int32"},
    "dest_url_distribution": {"kind": "zipf_over_pages", "exponent": 0.99},
    "visit_date_distribution": {
        "kind": "uniform", "low": 0, "high": 15930, "dtype": "int32"},
    "source_ip_distribution": {
        "kind": "dotted_quad",
        "octets": {"kind": "uniform", "low": 0, "high": 256}},
    "ad_revenue_distribution": {
        "kind": "uniform", "low": 0.0, "high": 1.0, "dtype": "float32"},
    "pages_per_visit": {"pages": 18, "visits": 155},
}

# the dotted quads of uservisits-q2, built by the same word arithmetic
_q2 = manifest.load_module(manifest.job_module_path("uservisits_q2"))


def resident(r):
    return r


def in_dates(r):
    # & and not `and`: the same expression has to trace on the device
    return (r[2] >= DATE_LOW) & (r[2] <= DATE_HIGH)


def by_url(r):
    return (r[0], (r[1], r[3]))


def by_ip(kv):
    return (kv[1][0][0], (kv[1][1], 1, kv[1][0][1]))


def add3(a, b):
    return (a[0] + b[0], a[1] + b[1], a[2] + b[2])


def revenue(kv):
    return kv[1][2]


def in_sample(kv):
    # the key begins b"173." ('1' '7' '3' '.')
    return ((kv[0][0] == 49) & (kv[0][1] == 55) & (kv[0][2] == 51)
            & (kv[0][3] == 46))


QUERIES = {"q3c": (in_dates, (DATE_LOW, DATE_HIGH))}


def page_urls(rng, n):
    """`n` distinct S100 URLs: "http://", the page's number in six
    base-26 letters (what makes them distinct), random lowercase letters
    up to a length drawn uniform over 20-100, NULs after it."""
    raw = rng.integers(97, 123, (n, URL_BYTES), dtype=np.uint8)
    raw[:, :len(URL_PREFIX)] = np.frombuffer(URL_PREFIX, np.uint8)
    number = np.arange(n, dtype=np.int64)
    for d in range(URL_DIGITS):
        raw[:, len(URL_PREFIX) + d] = 97 + number % 26
        number //= 26
    length = rng.integers(20, URL_BYTES + 1, n)
    raw *= np.arange(URL_BYTES)[None, :] < length[:, None]
    return raw.view("S%d" % URL_BYTES)[:, 0]


def make_data(config, traffic, seed, scale):
    """`resident_partitions` partitions of rows_per_job // scale visits,
    and round(all visits * 18 / 155) pages, drawn as the configuration's
    file says (a distribution this module lacks raises)."""
    for name, known in DISTRIBUTIONS.items():
        if config.get(name) != known:
            raise ValueError("%s %r is not implemented (known: %r)"
                             % (name, config.get(name), known))
    rows = max(1024, int(traffic["rows_per_job"]) // scale)
    nparts = int(traffic["resident_partitions"])
    ratio = DISTRIBUTIONS["pages_per_visit"]
    npages = int(round(rows * nparts * ratio["pages"] / ratio["visits"]))

    rng = np.random.default_rng([seed, 0x9A6E5])
    urls = page_urls(rng, npages)
    # popularity rank r (1 = hottest) of each page; the rank decides
    # how often the page is visited and its pageRank
    by_rank = rng.permutation(npages)
    high = DISTRIBUTIONS["page_rank_distribution"]["high"]
    page_rank = np.empty(npages, np.int32)
    page_rank[by_rank] = np.maximum(
        1, high / np.sqrt(np.arange(1, npages + 1))).astype(np.int32)
    weights = np.arange(1, npages + 1, dtype=np.float64) \
        ** -DISTRIBUTIONS["dest_url_distribution"]["exponent"]
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]

    tables = _q2._half_tables()
    days = DISTRIBUTIONS["visit_date_distribution"]["high"]

    def visits(p):
        rng = np.random.default_rng([seed, p])
        # the bounded Zipf by inverse CDF
        rank = np.minimum(np.searchsorted(cdf, rng.random(rows)),
                          npages - 1)
        octets = rng.integers(0, 256, (rows, 4), dtype=np.uint8)
        return (urls[by_rank[rank]], _q2.dotted_quads(octets, tables),
                rng.integers(0, days, rows).astype(np.int32),
                rng.random(rows, dtype=np.float32))

    with ThreadPoolExecutor(max_workers=4) as pool:     # numpy drops the GIL
        parts = list(pool.map(visits, range(nparts)))
    order = np.argsort(urls)
    return {"parts": parts, "rows": rows, "pages": (urls, page_rank),
            # the reference's index of the page table: URLs ascending
            "pages_sorted": (urls[order], page_rank[order])}


def input_rows(data):
    """Rows a job reads: the partition's visits and every page."""
    return data["rows"] + len(data["pages"][0])


def n_partitions(data):
    return len(data["parts"])


def resident_bytes(data):
    return (len(data["parts"]) * data["rows"] * VISIT_BYTES
            + len(data["pages"][0]) * PAGE_BYTES)


def _groups(data, part, query):
    """GROUP BY sourceIP over the partition's visits inside the dates,
    joined to their page: (keys ascending as an S16 array, sum of ranks,
    rows, float64 revenue, float64 sum of |revenue|), exact."""
    dest, ip, date, revenue_ = data["parts"][part]
    low, high = QUERIES[query][1]
    keep = (date >= low) & (date <= high)
    urls, ranks = data["pages_sorted"]
    at = np.minimum(np.searchsorted(urls, dest[keep]), len(urls) - 1)
    hit = urls[at] == dest[keep]            # pageURLs are distinct
    uniq, inv = np.unique(ip[keep][hit], return_inverse=True)
    v = revenue_[keep][hit].astype(np.float64)
    n = len(uniq)
    return (uniq,
            np.bincount(inv, weights=ranks[at[hit]],
                        minlength=n).astype(np.int64),
            np.bincount(inv, minlength=n),
            np.bincount(inv, weights=v, minlength=n),
            np.bincount(inv, weights=np.abs(v), minlength=n))


def reference(data, part, query, action):
    """What the job must return for this partition: every group (top1
    is judged against all of them), or the groups that begin
    SAMPLE_PREFIX (collect_sample)."""
    keys, ranks, counts, sums, magnitudes = _groups(data, part, query)
    if action == "top1":
        return keys, ranks, counts, sums, magnitudes
    if action == "collect_sample":
        keep = np.char.startswith(keys, SAMPLE_PREFIX)
        return (keys[keep], ranks[keep], counts[keep], sums[keep],
                magnitudes[keep])
    raise ValueError("unknown action %r" % action)


def _on_array_path(ctx, rdd, what):
    ex = ctx.scheduler.executor
    kinds = [str(st.get("kind")) for st in
             ctx.scheduler.history[-1]["stage_info"]]
    if rdd.id not in ex.result_cache_ids() or not kinds \
            or not all(k.startswith("array") for k in kinds) \
            or ctx.scheduler.fallback_reasons():
        raise RuntimeError(
            "the %s left the array path (stage kinds %s, resident %s, "
            "fallback %s): this program cannot run the configuration"
            % (what, kinds, rdd.id in ex.result_cache_ids(),
               ctx.scheduler.fallback_reasons()))


def _cache(ctx, columns, ndev):
    from dpark_tpu import Columns
    rdd = ctx.parallelize(Columns(*columns), ndev).map(resident).cache()
    if rdd.count() != len(columns[0]):
        raise RuntimeError("loading a table lost rows")
    return rdd


def load(ctx, data, ndev):
    """The page table and each partition of visits as cached RDDs
    resident in HBM.  First a PROOF_ROWS slice of each PROVES the path:
    a program that keeps 100-byte strings on the host would walk
    millions of rows through Python, so it is stopped here, in
    seconds."""
    for what, columns in (("rankings slice", data["pages"]),
                          ("uservisits slice", data["parts"][0])):
        proof = _cache(ctx, [c[:PROOF_ROWS] for c in columns], ndev)
        _on_array_path(ctx, proof, what)
    pages = _cache(ctx, data["pages"], ndev)
    parts = [_cache(ctx, columns, ndev) for columns in data["parts"]]
    _on_array_path(ctx, parts[-1], "uservisits table")
    return {"parts": parts, "pages": pages,
            "resident_ids": [pages.id] + [r.id for r in parts]}


def run(ctx, tables, part, query, action, ndev):
    """One job: the chain is built and its action returns inside the
    caller's clock."""
    grouped = tables["parts"][part].filter(QUERIES[query][0]).map(by_url) \
        .join(tables["pages"]).map(by_ip).reduceByKey(add3, ndev)
    if action == "top1":
        return grouped.top(1, key=revenue)
    if action == "collect_sample":
        return grouped.filter(in_sample).collect()
    raise ValueError("unknown action %r" % action)


def _rows_match(rows, expected):
    """Are the (sourceIP, (sum of ranks, rows, revenue)) rows groups of
    `expected`: keys exact as bytes, the two ints exact, the revenue
    within rows * 2**-23 * sum|v| of float64?  Returns their indices in
    `expected`, or None."""
    keys, ranks, counts, sums, magnitudes = expected
    got = np.array([k for k, _ in rows], dtype=keys.dtype)
    at = np.minimum(np.searchsorted(keys, got), max(len(keys) - 1, 0))
    if len(keys) == 0 or not (keys[at] == got).all():
        return None
    vals = np.array([v for _, v in rows], np.float64).reshape(len(rows), 3)
    exact = (vals[:, 0] == ranks[at]) & (vals[:, 1] == counts[at])
    close = np.abs(vals[:, 2] - sums[at]) \
        <= counts[at] * SUM_ULP * magnitudes[at]
    return at if bool(exact.all() and close.all()) else None


def verdict(result, expected, action):
    keys, _, counts, sums, magnitudes = expected
    if action == "collect_sample":
        if len(result) != len(keys):
            return False
        at = _rows_match(result, expected) if len(keys) else ()
        return at is not None and len(set(at)) == len(keys)
    # top1: one row, a group of the reference, whose float64 total could
    # be the float32 winner (sums that tie at the top may return either)
    if len(result) != 1:
        return False
    at = _rows_match(result, expected)
    if at is None:
        return False
    slack = counts * SUM_ULP * magnitudes
    best = float((sums - slack).max())
    g = int(at[0])
    if sums[g] < sums.max():
        print("[verdict] float32 tie at the top: returned group %d of "
              "total %.9g, the float64 largest is %.9g"
              % (g, sums[g], sums.max()), flush=True)
    return bool(sums[g] + slack[g] >= best)


def least(config, traffic, data, ndev, query):
    """The partition's four columns and the pages' two are read once and
    the groups written once; across chips the smaller side (the pages)
    crosses once to every other chip."""
    groups = len(_groups(data, 0, query)[0])
    pages = len(data["pages"][0])
    hbm = (data["rows"] * VISIT_BYTES + pages * PAGE_BYTES
           + groups * GROUP_BYTES) / ndev
    ici = pages * PAGE_BYTES * (ndev - 1) / ndev if ndev > 1 else 0
    return {"hbm_bytes": float(hbm), "ici_bytes": float(ici)}
