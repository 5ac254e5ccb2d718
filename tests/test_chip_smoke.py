"""chip_smoke.py's jobs and references at toy size on the CPU mesh
("tpu:2"): the same checks the chip run makes — answers against the
numpy references, every stage kind `array*`, no fallback or degrade
reason — plus the start-up refusals that keep a CPU from passing for
the chip."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402

N = 2           # mesh width of every job below


@pytest.fixture()
def tctx():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu:%d" % N)
    c.start()
    yield c
    c.stop()


def test_reduce_in_core(tctx):
    keys, vals = cs.make_pairs(0, 40_000, 1000)
    ref = cs.ref_reduce(keys, vals, 1000)
    cs.check_reduce(cs.job_reduce(tctx, keys, vals, N), ref, N)
    assert tctx.scheduler.executor.exchange_wire_bytes > 0


def test_reduce_in_waves(tctx):
    keys, vals = cs.make_pairs(0, 40_000, 1000)
    ref = cs.ref_reduce(keys, vals, 1000)
    result, pipeline = cs.job_reduce_waves(tctx, keys, vals, N, 2500)
    cs.check_reduce(result, ref, N)
    cs.check_waves(pipeline, 8)


def test_reference_catches_a_wrong_sum(tctx):
    keys, vals = cs.make_pairs(0, 4000, 100)
    exp_k, exp_v = cs.ref_reduce(keys, vals, 100)
    result = cs.job_reduce(tctx, keys, vals, N)
    with pytest.raises(cs.SmokeFailure):
        cs.check_reduce(result, (exp_k, exp_v + 1), N)


def test_sort(tctx):
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 1 << 40, 20_000, dtype=np.int64)
    vals = rng.integers(0, 1 << 16, 20_000, dtype=np.int64)
    cs.check_sort(cs.job_sort(tctx, keys, vals, N), keys, vals)


def test_join(tctx):
    fk, fv, dk, dv = cs.make_join(2, 20_000, 2000)
    ref = cs.ref_join_reduce(fk, fv, dk, dv, cs.JOIN_GROUPS)
    cs.check_keyed_sums(cs.job_join(tctx, fk, fv, dk, dv, N), ref)


def test_pagerank(tctx):
    ids, src, dst = cs.make_graph(3, 2048, cs.PR_DEGREE)
    ref = cs.ref_pagerank(2048, src, dst, cs.PR_STEPS, cs.PR_DAMPING)
    result = cs.job_pagerank(tctx, ids, src, dst, cs.PR_STEPS,
                             cs.PR_DAMPING, np.float32)
    cs.check_pagerank(result, ids, ref)
    assert tctx.scheduler._pregel_device_used is True


def test_service_second_submit_compiles_nothing():
    keys, vals = cs.make_pairs(4, 20_000, 1000)
    ref = cs.ref_reduce(keys, vals, 1000)
    out = cs.job_service(keys, vals, N, cs.CompileCounter().install(),
                         master="service:tpu:%d" % N)
    cs.check_service(out, ref)


def test_object_stage_fails_the_device_path_check(tctx):
    rows = tctx.parallelize([("a", 1), ("b", 2), ("a", 3)], N) \
        .reduceByKey(cs.add, N).collect()
    assert sorted(rows) == [("a", 4), ("b", 2)]
    with pytest.raises(cs.SmokeFailure):
        cs.assert_device_path(tctx.scheduler)


def test_main_refuses_a_cpu_platform(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and "[job]" not in out


def test_more_devices_than_exist_is_refused():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu:64")
    with pytest.raises(ValueError, match="more devices"):
        c.start()


def test_outside_compile_cache_dir_is_left_alone(monkeypatch):
    import jax
    from dpark_tpu.backend.tpu import executor
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    executor._place_compile_cache("tpu")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        executor._place_compile_cache("tpu")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    executor._place_compile_cache("cpu")
    assert jax.config.jax_compilation_cache_dir == before
