"""Test harness: force an 8-virtual-device CPU platform BEFORE jax import so
TPU-backend tests exercise real Mesh sharding without TPU hardware
(SURVEY.md section 4: the local master is the golden model; every backend
test asserts backend output == local output)."""

import os
import shutil

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORM_NAME"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DPARK_PROGRESS", "0")

# mesh-marked tests (full 8-virtual-device collectives) need roughly
# one host CPU per mesh device: an 8-device all_to_all on a 2-CPU
# container wedges in the XLA:CPU intra-process rendezvous and the
# whole tier-1 run dies in the suite timeout instead of finishing with
# skips.  conf.MESH_TEST_DEVICES is the knob (DPARK_MESH_TEST_DEVICES;
# 0 forces the tests to run regardless of CPU count).  Tests on small
# sliced meshes ("tpu:2") stay unmarked — they fit tiny containers.

# a jax imported before this file ran has already read the env var;
# ask through the config API as well.  jax is optional for the
# pure-host tests.
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass

import pytest


def pytest_collection_modifyitems(config, items):
    from dpark_tpu import conf
    want = conf.MESH_TEST_DEVICES
    have = os.cpu_count() or 1
    if not want or have >= want:
        return
    skip = pytest.mark.skip(
        reason="mesh test needs >= %d CPUs for the %d-device virtual "
               "mesh (host has %d); set DPARK_MESH_TEST_DEVICES=0 to "
               "force" % (want, want, have))
    for item in items:
        if "mesh" in item.keywords:
            item.add_marker(skip)


def shuffled_on_device(ctx):
    """Did a job of this context write a shuffle on the array path?
    Read from the job records: the executor's shuffle_store holds only
    the stores of chains that are still held (a store lives as long as
    the RDD that shuffled it)."""
    return any(st.get("shuffle") and str(st.get("kind")).startswith("array")
               for rec in ctx.scheduler.history
               for st in rec["stage_info"])


def load_tool(name):
    """Import one of the extensionless tools/ CLIs (dtrace, ...) or a
    tools/*.py script as a module — shared by every tool-driving
    test."""
    import importlib.machinery
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name)
    modname = "_tool_%s" % name.replace(".", "_")
    loader = importlib.machinery.SourceFileLoader(modname, path)
    spec = importlib.util.spec_from_loader(modname, loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


@pytest.fixture()
def ctx():
    from dpark_tpu import DparkContext
    c = DparkContext("local")
    yield c
    c.stop()


@pytest.fixture()
def pctx():
    from dpark_tpu import DparkContext
    c = DparkContext("process:4")
    yield c
    c.stop()


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_grade():
    """With DPARK_LOCKCHECK=record armed over the whole suite (the CI
    lockcheck job), fail the RUN if the merged acquisition-order graph
    drew any cycle — even one whose threads got lucky and never
    wedged.  Off (the default) this is a no-op."""
    yield
    from dpark_tpu import locks
    san = locks.sanitizer()
    if san is None:
        return
    rep = san.report()
    if rep["cycles"] or rep["findings"]:
        raise AssertionError(
            "lock sanitizer observed ordering hazards across the "
            "suite:\n%s" % locks.render_report(rep))


@pytest.fixture(autouse=True)
def _fresh_env(tmp_path_factory):
    """Each test gets its own workdir; the env singleton is reset."""
    from dpark_tpu.env import env
    import dpark_tpu.context as context_mod
    was = env.started
    env.stop()
    env.__init__()
    env.start(is_master=True,
              environ={"DPARK_WORKDIR":
                       str(tmp_path_factory.mktemp("dpark-work"))})
    yield
    env.stop()
    env.__init__()
