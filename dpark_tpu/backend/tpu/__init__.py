"""The `-m tpu` master: DAG scheduling on the driver, stages as fused SPMD
programs on the device mesh.

Reference parity: replaces dpark's MesosScheduler + executor + file shuffle
(SURVEY.md section 3.1 "TPU mapping"): everything below submitMissingTasks
becomes one shard_map program per stage; narrow hot loops fuse into the
stage program; the shuffle hop is all_to_all + segmented reduce.  Stages
whose user code is not jnp-traceable fall back to the in-process object
path — graceful degradation, never an error (SURVEY.md 7.2 item 1).
"""

from dpark_tpu import trace
from dpark_tpu.env import env
from dpark_tpu.schedule import DAGScheduler, _run_task_inline
from dpark_tpu.task import ResultTask
from dpark_tpu.utils.log import get_logger

logger = get_logger("tpu")


# the known XLA:CPU capability gap (PR 2 notes): collective/aliasing
# programs over a PROCESS-SPANNING mesh raise "Multiprocess
# computations aren't implemented on the CPU backend".  Real TPU/GPU
# pods implement them; a CPU-emulated multi-controller run records
# this as the stage's fallback_reason and serves the job through the
# object path instead of dying on a raw assert (ISSUE 12 satellite).
SPMD_CPU_FALLBACK = ("multi-controller SPMD unsupported on the CPU "
                     "backend (XLA:CPU implements no cross-process "
                     "computations); object path")


def _multiproc_cpu_gap(e):
    """Is this the CPU backend refusing a cross-process computation
    (a CAPABILITY gap, not a runtime fault)?  Matched by message: XLA
    raises it as a plain JaxRuntimeError with no distinct type."""
    for exc in (e, getattr(e, "__cause__", None)):
        if exc is None:
            continue
        text = str(exc)
        if "Multiprocess computations" in text:
            return True
        if "implemented" in text and "CPU backend" in text:
            return True
    return False


def _device_error(e):
    """Is this a device RUNTIME error (JaxRuntimeError, HBM
    RESOURCE_EXHAUSTED) — the class the stage-level degradation ladder
    owns — as opposed to a plan/user-code error?  The RESOURCE_EXHAUSTED
    text also classifies the injected stand-ins that are not jax errors
    (the emulated wave ceiling's MemoryError)."""
    import jax
    for exc in (e, getattr(e, "__cause__", None)):
        if exc is None:
            continue
        if isinstance(exc, jax.errors.JaxRuntimeError):
            return True
        if "RESOURCE_EXHAUSTED" in str(exc):
            return True
    return False


class _PregelRun:
    """What _new_job_record reads of a job's final RDD, for a job that
    has none: a Pregel run, named for the user's call."""

    def __init__(self):
        from dpark_tpu.utils import user_call_site
        self.scope_name = "Pregel@%s" % user_call_site()


class TPUScheduler(DAGScheduler):
    # plan analysis mutates module state (fuse.last_fallback_reason)
    # and probes with shared tracers: with a resident job server's
    # slot threads (ISSUE 9) analyzing concurrently, serialize it so
    # the recorded fallback reason belongs to the stage it names
    _analyze_lock = __import__("threading").Lock()

    def __init__(self, ndev=None):
        super().__init__()
        self._requested_ndev = ndev
        self.executor = None

    def start(self):
        super().start()
        if self.executor is None:
            import jax
            # select the mesh platform before backend init (e.g. `cpu`
            # with --xla_force_host_platform_device_count for a
            # virtual mesh)
            from dpark_tpu.utils import apply_platform_override
            apply_platform_override()
            from dpark_tpu.backend.tpu.executor import JAXExecutor
            devices = jax.devices()
            if devices[0].platform == "cpu" \
                    and not jax.config.jax_platforms:
                # jax fell back to the CPU because no accelerator
                # initialised: the job would run there and look
                # healthy.  A CPU mesh must be asked for
                # (JAX_PLATFORMS / DPARK_TPU_PLATFORM = cpu).
                raise RuntimeError(
                    "the tpu master found no accelerator (jax.devices() "
                    "is %s); set JAX_PLATFORMS=cpu or "
                    "DPARK_TPU_PLATFORM=cpu to run on a CPU mesh "
                    "deliberately" % (devices,))
            if self._requested_ndev:
                if self._requested_ndev > len(devices):
                    raise ValueError(
                        "master tpu:%d asks for more devices than the "
                        "%d %s device(s) present"
                        % (self._requested_ndev, len(devices),
                           devices[0].platform))
                devices = devices[:self._requested_ndev]
            self.executor = JAXExecutor(devices)
            # an accelerator that reports no memory limit fails here,
            # at start, not inside a stage's analysis
            from dpark_tpu import conf
            conf._hbm_bytes_limit()
            # HBM eviction spills re-point stage output locations
            # (ISSUE 9 satellite): a later job reusing an available
            # stage must see the disk uris, not stale hbm:// ones
            self.executor._spill_notify = self._on_store_spilled
            # a store lives as long as its ShuffleDependency: eviction
            # releases what nothing can read before it picks victims
            self.executor._release_unreachable = self._release_unreachable
            logger.info("tpu master on %d %s device(s)",
                        len(devices), devices[0].platform)

    def _on_store_spilled(self, sid, uri):
        stage = self.shuffle_to_stage.get(sid)
        if stage is None:
            return
        for i, loc in enumerate(stage.output_locs):
            if loc and str(loc).startswith("hbm://"):
                stage.output_locs[i] = uri

    def _release_unreachable(self):
        """The base's drain under the mesh lock, which every other
        writer of the executor's store accounting holds (a stage of
        another driver thread's job may be registering or evicting).
        Never waits for it: while another job's stage holds the mesh
        the queue stays, and that stage's own eviction, which calls
        this with the lock held, drains it before it picks victims."""
        if not self._unreachable:
            return
        ex = self.executor
        if ex is None:
            return super()._release_unreachable()
        lock = ex._mesh_lock
        if lock.try_enter():
            try:
                self._drain_unreachable(ex)
            finally:
                lock.__exit__(None, None, None)

    def _drain_unreachable(self, ex):
        """The base's drain; with the trace plane on, one
        `store.release` span a drain that dropped a store (args:
        stores, bytes), stamped after the fact under the job this
        thread is running: the drain only learns what it frees by
        freeing it."""
        plane = trace._PLANE
        if plane is not None:
            import time as _time
            stores, nbytes = ex.stores_released, ex._store_bytes
            t0 = _time.time()
            super()._release_unreachable()
            stores = ex.stores_released - stores
            if stores:
                record = self._current_record
                trace.emit("store.release", "exec", t0,
                           _time.time() - t0, stores=stores,
                           bytes=nbytes - ex._store_bytes,
                           job=record["id"] if record else None)
        else:
            super()._release_unreachable()

    def _shuffle_unreachable(self, sid):
        ex = self.executor
        if ex is not None and sid in ex.shuffle_store:
            ex.stores_released += 1
            ex.drop_shuffle(sid, reason="unreachable")

    def _job_started(self, record):
        """Pin this job's HBM buckets against disk spill and snapshot
        the program-cache counters.  The snapshot is only the FALLBACK
        for probes no thread tagged; since ISSUE 15 the cache counts
        hits/misses per job exactly (the probing thread's job stamp),
        so concurrent jobs' record["program_cache"] deltas no longer
        overlap — the PR 9 caveat is closed."""
        super()._job_started(record)
        ex = self.executor
        if ex is not None:
            ex.live_jobs.add(record["id"])
            record["_pc_base"] = True

    def _job_finished(self, record):
        super()._job_finished(record)
        ex = self.executor
        if ex is None:
            return
        ex.live_jobs.discard(record["id"])
        if record.pop("_pc_base", None) is None:
            return
        # exact per-job attribution (ISSUE 15 satellite): every stage
        # submission stamps the executing thread with the job id, so
        # the cache's per-job buckets carry this job's own probes —
        # exact even while other jobs compile concurrently.  A job
        # with no tagged probes (pure host work) reads 0/0, which is
        # the truth the old process-wide delta could not tell.
        record["program_cache"] = ex._compiled.job_stats(record["id"])

    def stop(self):
        super().stop()
        if self.executor is not None:
            self.executor.stop()
            self.executor = None

    def default_parallelism(self):
        self.start()
        return self.executor.ndev

    def submit_tasks(self, stage, tasks, report):
        self.start()
        import time as _time

        from dpark_tpu import adapt
        from dpark_tpu.backend.tpu import fuse
        # stamp the job this thread is executing for (ISSUE 9): the
        # executor tags shuffle stores with it so the HBM eviction
        # arbiter knows which buckets belong to live jobs
        record = self._current_record
        self.executor._job_tls.job = \
            record["id"] if record is not None else None
        plan = None
        adapt_sig = None
        if len(tasks) >= stage.num_partitions:
            # single-task retries skip the array path: run_stage always
            # processes all partitions, so replaying it for one failed
            # task would redo the whole stage
            with self._analyze_lock:
                analysis_gap = False
                try:
                    plan = self._analyze(stage)
                except Exception as e:
                    logger.warning("analysis failed for %s: %s: %s",
                                   stage, type(e).__name__, e)
                    analysis_gap = _multiproc_cpu_gap(e)
                reason = None if plan is not None \
                    else fuse.last_fallback_reason()
                if plan is None and not reason and analysis_gap:
                    # the CPU backend's multi-controller gap raised
                    # during analysis itself: record the capability
                    # reason, not silence (ISSUE 12 satellite)
                    reason = SPMD_CPU_FALLBACK
            if plan is None:
                if reason:
                    # why the plan left the array path (key shape,
                    # non-numeric leaf, ...): rides the per-stage job
                    # record next to kind=object, and the
                    # host-fallback-key lint rule reports the same
                    # answer pre-flight
                    self.note_stage(stage.id, fallback_reason=reason)
            elif adapt.enabled():
                # off mode pays nothing past this flag check — the
                # signature (sha1 over the stable program-key repr)
                # is only worth computing when observations record
                with self._adapt_span("choose"):
                    try:
                        adapt_sig = fuse.plan_adapt_signature(plan)
                    except Exception:
                        adapt_sig = None
                    # cost model (ISSUE 7 decision point 2): with
                    # recorded ms for BOTH paths of this program class,
                    # the cheaper one wins — predicted, not assumed,
                    # admission.  The choice is per stage and recorded
                    # as `adapt_reason` (the cost-model sibling of
                    # fallback/degrade_reason).
                    choice = adapt.choose_path(adapt_sig)
                if choice is not None and choice["choice"] == "object":
                    self.note_stage(stage.id,
                                    adapt_reason=choice["reason"])
                    plan = None
        if plan is not None:
            # the mesh lock spans the WHOLE degradable run, not just
            # run_stage: the OOM ladder swaps conf.STREAM_CHUNK_ROWS
            # around its retry, which must stay invisible to another
            # job's concurrently dispatched device stage (ISSUE 9)
            with self.executor._mesh_lock:
                handled = self._run_degradable(stage, tasks, plan,
                                               report)
            if handled:
                return
        # object path: run tasks inline on the driver (golden semantics);
        # cogroup stages first pre-materialize their CoGroupedRDD via the
        # device exchange so only the group-merge runs in Python
        t0 = _time.time()
        precomputed = None
        try:
            precomputed = self._precompute_join(stage)
        except Exception as e:
            logger.warning("device join skipped: %s: %s",
                           type(e).__name__, e)
        if precomputed is None:
            try:
                precomputed = self._precompute_cogroup(stage)
            except Exception as e:
                logger.warning("cogroup precompute skipped: %s: %s",
                               type(e).__name__, e)
        all_ok = False
        from dpark_tpu import bulkplane
        rx0 = bulkplane.total_received_bytes()
        try:
            statuses = []
            for task in tasks:
                status, payload = _run_task_inline(task)
                statuses.append(status)
                report(task, status, payload)
            all_ok = all(s == "success" for s in statuses)
            self._note_remote_fetch(stage.id, rx0)
        finally:
            if precomputed is not None:
                # free the seeded partitions (unless the USER cached this
                # cogroup): later retries recompute through the export
                # bridge instead of leaking the dataset in driver memory
                cg, nparts, was_cached = precomputed
                if not was_cached:
                    from dpark_tpu.env import env
                    env.cache.drop(cg.id, nparts)
                    cg.should_cache = False
        # an analyzable stage that ran the object path CLEANLY
        # (cost-model choice, analysis-time fallback with a plan, or
        # runtime degrade) feeds the cost model its observed host ms —
        # a failed/fetch-failed attempt must NOT record its short wall
        # as a valid host cost (it would wrongly cheapen the object
        # path and steer future runs off the device)
        if adapt_sig is not None and all_ok:
            adapt.observe_path(adapt_sig, "host",
                               (_time.time() - t0) * 1e3)

    def run_pregel(self, on_device, on_host, load_error=None):
        """One Pregel run (bagel.PregelGraph.run) as a job of this
        scheduler: a record in `history` as runJob leaves one (id,
        scope, state, seconds, stage_info) and, with the trace plane
        on, `job.begin`, then `job` > `stage.run` > `stage.exec` around
        the supersteps with the job's id on every span, then
        `job.finish`; no lineage is walked and nothing is linted, so no
        `preflight`.  `on_device()` runs the supersteps under the mesh
        lock, as run_stage runs a stage, and the job's one stage is
        then of kind `array+pregel`, with its superstep and message
        counts.  If it raises anything but a fault of the input, or the
        device holds no graph (`load_error` says why), the stage stays
        of kind `object`, carries the cause as its `fallback_reason`,
        and `on_host()`, the numpy loop, answers inside the same job."""
        import time as _time
        from dpark_tpu.schedule import Stage
        self.start()
        ex = self.executor
        with trace.span("job.begin", "sched") as sp:
            record = self._new_job_record(_PregelRun(), ex.ndev)
            if trace._PLANE is not None:
                sp.args.update(job=record["id"], stages=1)
        t0 = _time.time()
        stage_id = next(Stage._next_id)
        ex._job_tls.job = record["id"]
        try:
            with trace.ctx(job=record["id"], stage=stage_id), \
                    trace.span("stage.run", "sched", tasks=ex.ndev,
                               shuffle=False):
                out = self._run_pregel_stage(stage_id, on_device,
                                             on_host, load_error)
            record["finished"] = record["parts"]
            record["state"] = "done"
            return out
        except BaseException:
            record["state"] = "aborted"
            raise
        finally:
            self._finish_job(record, t0)

    def _run_pregel_stage(self, stage_id, on_device, on_host, reason):
        import time as _time
        from dpark_tpu.bagel import (PregelInputError, _first_line,
                                     _NotColumnarizable)
        ex = self.executor
        if reason is None:
            t0 = _time.time()
            steps0, msgs0 = ex.pregel_supersteps, ex.pregel_messages
            rows0 = self._exchange_rows()
            try:
                with ex._mesh_lock, \
                        trace.span("stage.exec", "exec", source="pregel"):
                    out = on_device()
            except (PregelInputError, _NotColumnarizable):
                raise      # wrong on the host path too: surface it
            except Exception as e:
                reason = "device Pregel failed: " + _first_line(e)
                logger.warning("%s; the host loop answers", reason)
            else:
                self.note_stage(
                    stage_id, kind="array+pregel",
                    run_seconds=round(_time.time() - t0, 3),
                    supersteps=ex.pregel_supersteps - steps0,
                    messages=ex.pregel_messages - msgs0,
                    **self._exchange_note(rows0))
                self._pregel_device_used = True
                return out
        self.note_stage(stage_id, fallback_reason=reason)
        self._pregel_device_used = False
        return on_host()

    def _adapt_span(self, step):
        """With the trace plane on, the `adapt.path` span around the
        cost model's part of a stage's submission (`step`: choose, the
        plan's signature and choose_path before the stage runs;
        observe, observe_path's append to the adapt store after it)."""
        plane = trace._PLANE
        if plane is not None:
            return trace.span("adapt.path", "adapt", step=step)
        return trace._NOOP

    def _analyze(self, stage):
        """fuse.analyze_stage; with the trace plane on, one `plan` span
        (driver planning of one stage; `ok`: a plan came back)."""
        from dpark_tpu.backend.tpu import fuse
        ex = self.executor
        plane = trace._PLANE
        if plane is not None:
            with trace.span("plan", "exec", ok=False) as sp:
                plan = fuse.analyze_stage(stage, ex.ndev, ex)
                sp.args["ok"] = plan is not None
                return plan
        return fuse.analyze_stage(stage, ex.ndev, ex)

    def _spill_write_failed(self, stage, tasks, report, e):
        """ENOSPC & co mid-spill: NOT a device fault, and the object
        path would spill to the same disk — surface it on the stage's
        tasks as task failures so the scheduler's retry/escalation
        accounting owns it (single-task retries then run the object
        path inline).  Never a silent fallback, never a job abort
        before MAX_TASK_FAILURES."""
        logger.warning("spill write failed for %s: %s", stage, e)
        self.note_stage(stage.id,
                        degrade_reason="spill write failed: %s" % e)
        for task in tasks:
            report(task, "failed", "spill write failed: %s" % e)

    def _run_degradable(self, stage, tasks, plan, report):
        """Array path with runtime graceful degradation (ISSUE 5
        tentpole): a device runtime error (JaxRuntimeError /
        RESOURCE_EXHAUSTED) first retries the stage with a HALVED wave
        budget — an HBM OOM usually just means the auto-sized wave was
        too greedy — then falls back to the object path for THIS STAGE
        ONLY.  Each step is recorded as the stage's `degrade_reason`
        (the runtime mirror of `fallback_reason`); the job never
        aborts on a device error.  Returns True when the stage was
        fully reported (success or surfaced task failures); False
        means "run the object path".

        FLOAT CAVEAT (documented in README): an object-path fallback
        of a reassociated float aggregate can differ in low-order bits
        from the device fold — same contract as GROUP_AGG_REWRITE.
        Integer workloads (the chaos parity suite) are exact."""
        from dpark_tpu import conf
        from dpark_tpu.backend.tpu.executor import CombineOverflow
        from dpark_tpu.shuffle import SpillWriteError
        try:
            self._run_array_stage(stage, tasks, plan, report)
            self._adapt_note_stream_budget()
            return True
        except SpillWriteError as e:
            self._spill_write_failed(stage, tasks, report, e)
            return True
        except CombineOverflow:
            raise       # the store itself is short: no path answers
        except Exception as e:
            if _multiproc_cpu_gap(e):
                # a CAPABILITY gap, not a runtime fault: the CPU
                # backend implements no cross-process computations
                # (pre-existing per PR 2 notes).  Record it as the
                # stage's fallback_reason — the SPMD dryrun reads it
                # to SKIP cleanly instead of raw-asserting — and
                # serve the stage through the object path.
                logger.warning(
                    "array path unavailable for %s (%s); object path",
                    stage, SPMD_CPU_FALLBACK)
                self.note_stage(stage.id,
                                fallback_reason=SPMD_CPU_FALLBACK)
                return False
            if not _device_error(e):
                logger.warning(
                    "array path failed for %s (%s); object fallback",
                    stage, e)
                self.note_stage(stage.id, degrade_reason=(
                    "array path error (%s: %s); object path"
                    % (type(e).__name__, str(e)[:160])))
                self._adapt_observe_device_error(plan)
                return False
            first = "%s: %s" % (type(e).__name__, str(e)[:160])
        # degrade step 1: halve the wave budget and retry the stage.
        # Device errors raise during run_stage, BEFORE any task is
        # reported, so the whole-stage retry cannot double-report.
        # The budget is applied through conf.STREAM_CHUNK_ROWS (not a
        # per-plan field) DELIBERATELY: fuse._big_columnar's streaming
        # eligibility reads the same knob, so halving can flip an
        # in-core stage that OOM'd onto the wave stream — the actual
        # cure.  Safe because this scheduler runs stages serially on
        # the event-loop thread (restored in the finally); a future
        # parallel-stage scheduler must thread it through the plan.
        old = conf.STREAM_CHUNK_ROWS
        row_bytes = 16
        if isinstance(old, int):
            eff = old
        else:
            # "auto" sizes waves to HBM / row WIDTH: halve the budget
            # the executor actually used, not the 16-byte-row default
            # (for wide rows that default is a LARGER wave than the
            # one that just OOM'd)
            try:
                from dpark_tpu.backend.tpu import fuse
                if plan.source[0] == "ingest":
                    row_bytes = fuse._columnar_row_bytes(
                        plan.source[1]._slices)
            except Exception:
                pass
            eff = conf.stream_chunk_rows(row_bytes)
        halved = max(64, int(eff) // 2)
        # the ladder's outcomes feed the adaptive store (ISSUE 7): the
        # budget that OOM'd is recorded as failing NOW — even if the
        # job ultimately falls back to the object path, the next run
        # of this row-width class starts below the failed rung instead
        # of re-OOMing.  A user-pinned budget records nothing (pins
        # bypass the auto derivation entirely).
        from dpark_tpu import adapt
        auto_budget = not isinstance(old, int)
        if auto_budget:
            adapt.record_wave_budget(row_bytes, int(eff), ok=False,
                                     source="oom")
        conf.STREAM_CHUNK_ROWS = halved
        logger.warning("device error on %s (%s); retrying with halved "
                       "wave budget (%d rows/device)", stage, first,
                       halved)
        try:
            self._run_array_stage(stage, tasks, plan, report)
            self.note_stage(stage.id, degrade_reason=(
                "%s; stage retried with halved wave budget "
                "(%d rows/device)" % (first, halved)))
            if auto_budget:
                adapt.record_wave_budget(row_bytes, halved, ok=True,
                                         source="oom_ladder")
            return True
        except SpillWriteError as e:
            self._spill_write_failed(stage, tasks, report, e)
            return True
        except Exception as e2:
            # degrade step 2: object path for this stage only
            logger.warning(
                "halved-wave retry failed for %s (%s); object "
                "fallback for this stage", stage, e2)
            self.note_stage(stage.id, degrade_reason=(
                "%s; halved-wave retry failed (%s: %s); object path "
                "for this stage" % (first, type(e2).__name__,
                                    str(e2)[:120])))
            if auto_budget:
                # a halved rung that failed for a NON-memory reason
                # still did not OOM — it is the ladder's final working
                # budget and the next run seeds from it; a rung that
                # OOM'd again records as failing, so the next run
                # starts below it
                adapt.record_wave_budget(row_bytes, halved,
                                         ok=not _device_error(e2),
                                         source="oom_ladder")
            self._adapt_observe_device_error(plan)
            return False
        finally:
            conf.STREAM_CHUNK_ROWS = old

    def _adapt_observe_device_error(self, plan):
        """Count a device-path failure for this program class in the
        adaptive store (observability; path pricing needs observed ms
        on both sides and never decides on errors alone)."""
        try:
            from dpark_tpu import adapt
            from dpark_tpu.backend.tpu import fuse
            if adapt.enabled():
                adapt.observe_path(fuse.plan_adapt_signature(plan),
                                   "device", error=True)
        except Exception:
            pass

    def _adapt_note_stream_budget(self):
        """Persist the wave budget a successful auto-sized streamed
        stage ran with as known-good (ISSUE 7): the next run of this
        row-width class seeds from it instead of re-deriving.  Pinned
        budgets (tests, the ladder's halved retry) record via the
        ladder paths, not here."""
        from dpark_tpu import adapt, conf
        ex = self.executor
        if (ex.last_stream_stats is not None
                and ex.last_wave_budget is not None
                and conf.STREAM_CHUNK_ROWS == "auto"):
            budget, row_bytes = ex.last_wave_budget
            adapt.record_wave_budget(row_bytes, budget, ok=True,
                                     source="stream")

    def _resident_nocombine_deps(self, cg):
        """All of a CoGroupedRDD's inputs as HBM-resident no-combine
        shuffle deps, or None (narrow side / host-resident / combining).
        fuse._analyze_join_source, the array-path twin, additionally
        rejects encoded keys and r > mesh, which the host-seeding paths
        here tolerate; the records' own check is fuse.join_sides for
        both."""
        from dpark_tpu.backend.tpu import fuse
        deps = []
        for kind, obj in cg._dep_kinds:
            if kind != "shuffle" or not fuse.is_list_agg(obj.aggregator) \
                    or not self.executor.has_shuffle(obj.shuffle_id):
                return None
            if "host_runs" in self.executor.shuffle_store[
                    obj.shuffle_id]:
                return None      # spilled runs: host merge consumes them
            deps.append(obj)
        return deps

    def _precompute_join(self, stage):
        """Full device join: when the stage's top RDD is exactly
        a.join(b) over two HBM-resident no-combine shuffles, expand the
        key-matched pairs on device and seed the join RDD's partitions."""
        from dpark_tpu.backend.tpu import fuse
        from dpark_tpu.env import env
        from dpark_tpu.rdd import (CoGroupedRDD, FlatMappedValuesRDD,
                                   _join_values)
        top = stage.rdd
        if not (isinstance(top, FlatMappedValuesRDD)
                and top.f is _join_values
                and isinstance(top.prev, CoGroupedRDD)
                and len(top.prev.rdds) == 2):
            return None
        if getattr(top, "_tpu_precomputed", False):
            return None
        cg = top.prev
        deps = self._resident_nocombine_deps(cg)
        if deps is None:
            return None
        if fuse.join_sides([self.executor.shuffle_store[d.shuffle_id]
                            for d in deps]) is None:
            return None
        rows_per_part = self.executor.run_device_join(deps[0], deps[1])
        for p, rows in enumerate(rows_per_part):
            env.cache.put((top.id, p), rows, disk=False)
        was_cached = top.should_cache
        top.should_cache = True
        top._tpu_precomputed = True
        logger.debug("join %d expanded on device", top.id)
        return top, len(rows_per_part), was_cached

    def _precompute_cogroup(self, stage):
        """If this stage reads a CoGroupedRDD whose inputs are all
        HBM-resident no-combine shuffles, run the exchanges on device
        (sorted rows per partition), merge the sorted runs on host, and
        seed the partition cache so the object path never touches the
        per-bucket export bridge."""
        from dpark_tpu.backend.tpu import fuse
        from dpark_tpu.dependency import ShuffleDependency
        from dpark_tpu.env import env
        from dpark_tpu.rdd import CoGroupedRDD

        # find the nearest CoGroupedRDD through narrow deps
        seen = set()
        cg = None
        frontier = [stage.rdd]
        while frontier:
            r = frontier.pop()
            if id(r) in seen:
                continue
            seen.add(id(r))
            if isinstance(r, CoGroupedRDD):
                cg = r
                break
            for d in r.dependencies:
                if not isinstance(d, ShuffleDependency):
                    frontier.append(d.rdd)
        if cg is None:
            return None
        if getattr(cg, "_tpu_precomputed", False):
            return None
        deps = self._resident_nocombine_deps(cg)
        if deps is None:
            return None
        per_source = [self.executor.gather_rows(dep) for dep in deps]
        nsrc = len(per_source)
        nparts = cg.partitioner.num_partitions
        for p in range(nparts):
            slots = {}
            for si in range(nsrc):
                for k, v in per_source[si][p]:
                    slot = slots.get(k)
                    if slot is None:
                        slot = slots[k] = tuple([] for _ in range(nsrc))
                    slot[si].append(v)
            env.cache.put((cg.id, p), list(slots.items()), disk=False)
        was_cached = cg.should_cache
        cg.should_cache = True
        cg._tpu_precomputed = True
        logger.debug("cogroup %d precomputed on device (%d sources)",
                     cg.id, nsrc)
        return cg, nparts, was_cached

    def _exchange_rows(self):
        """The executor's exchange accounting, to take a stage's part of
        it from (_exchange_note)."""
        ex = self.executor
        return (ex.exchange_wire_bytes, ex.exchange_real_rows,
                ex.exchange_slot_rows, ex.ingest_slot_rows)

    def _exchange_note(self, rows0):
        """What a stage's exchanges moved since `rows0`, as entries of
        its stage note (one read of the deferred row counts where a
        one-device exchange left some)."""
        ex = self.executor
        wire0, real0, slot0, islot0 = rows0
        wire = ex.exchange_wire_bytes - wire0
        slot_rows = ex.exchange_slot_rows - slot0
        ingest_rows = ex.ingest_slot_rows - islot0
        if wire or slot_rows:
            # per-stage exchange accounting (the slot-sizing tuning
            # signals, visible in the web UI)
            return {"wire_bytes": wire, "pad_efficiency": round(
                (ex.exchange_real_rows - real0) / max(1, slot_rows), 4)}
        if ingest_rows:
            # single-chip identity exchange: no wire moved; report the
            # ingest slot fill under its own name so the UI never
            # presents ingest padding as wire padding
            return {"ingest_pad_efficiency": round(
                (ex.exchange_real_rows - real0) / max(1, ingest_rows), 4)}
        return {}

    def _run_array_stage(self, stage, tasks, plan, report):
        import time as _time
        from dpark_tpu.backend.tpu import fuse
        from dpark_tpu.rdd import _count_iter, _PartReduce
        t0 = _time.time()
        # count() needs no rows on the driver — the object path sums
        # per-executor counts, and the array path can answer straight
        # from the device counts leaf, skipping the whole egest (one
        # scalar read per device instead of every row crossing D2H)
        plan.count_only = (not stage.is_shuffle_map and bool(tasks)
                           and all(isinstance(t, ResultTask)
                                   and t.func is _count_iter
                                   for t in tasks))
        # top(k): per-device pre-top with a classifiable ordering key —
        # ndev*k rows egest instead of the whole batch; the per-task
        # _TopN and the driver heap merge then run unchanged
        from dpark_tpu.rdd import _TopN
        plan.top_candidate = None
        if (not stage.is_shuffle_map and tasks
                and all(isinstance(t, ResultTask)
                        and isinstance(t.func, _TopN)
                        for t in tasks)
                and len({(t.func.n, id(t.func.key), t.func.smallest)
                         for t in tasks}) == 1):
            tf = tasks[0].func
            plan.top_candidate = (tf.n, tf.key, tf.smallest)
        plan.topk_used = False
        # sortByKey's bounds sample: the first n keys of a partition
        # are sliced on the device and only they reach the host
        from dpark_tpu.rdd import _TakeSampleKeys
        plan.sample_keys = None
        if (not stage.is_shuffle_map and tasks
                and all(isinstance(t, ResultTask)
                        and isinstance(t.func, _TakeSampleKeys)
                        for t in tasks)
                and len({t.func.n for t in tasks}) == 1):
            plan.sample_keys = tasks[0].func.n
        # reduce(f) with a PROVABLE monoid over scalar records likewise
        # answers from one per-device reduction (ndev scalars on the
        # wire); unprovable reduces keep the egest + host fold
        plan.reduce_monoid = None
        if (not stage.is_shuffle_map and tasks
                and all(isinstance(t, ResultTask)
                        and isinstance(t.func, _PartReduce)
                        for t in tasks)
                and len({id(t.func.f) for t in tasks}) == 1):
            try:
                plan.reduce_monoid = fuse.classify_merge(
                    tasks[0].func.f)
            except Exception:
                plan.reduce_monoid = None
        rows0 = self._exchange_rows()
        # live per-wave pipeline updates: a long streamed stage reports
        # its ingest/compute/exchange/spill ms and device-idle fraction
        # into stage_info WHILE it runs (web UI), not just at the end
        self.executor._stage_note = (
            lambda **kw: self.note_stage(stage.id, **kw))
        try:
            kind, result = self.executor.run_stage(plan)
        finally:
            self.executor._stage_note = None
        note = {"kind": "array",
                "run_seconds": round(_time.time() - t0, 3)}
        if self.executor.last_stream_stats is not None:
            note["pipeline"] = self.executor.last_stream_stats
        note.update(self._exchange_note(rows0))
        if kind == "shuffle":
            store = self.executor.shuffle_store.get(result)
            if store is not None:
                note["hbm_bytes"] = store.get("nbytes", 0)
                if "host_runs" in store:
                    note["kind"] = "array+spill"
            uri = "hbm://%d" % result
            for task in tasks:
                report(task, "success", (uri, {}, {}))
        elif kind in ("counts", "sampled"):
            if kind == "counts":
                note["kind"] = "array+counts"    # observable: no egest ran
            for task in tasks:
                report(task, "success", (result[task.partition], {}, {}))
        elif kind == "reduced":
            from dpark_tpu.rdd import _EMPTY
            note["kind"] = "array+reduced"
            for task in tasks:
                v, n = result[task.partition]
                report(task, "success",
                       (v if n else _EMPTY, {}, {}))
        else:
            if getattr(plan, "topk_used", False):
                note["kind"] = "array+top"   # observable: pre-top ran
            rows_per_part = result
            sp = trace._NOOP
            plane = trace._PLANE
            if plane is not None:
                sp = trace.span("result.rows", "sched", tasks=len(tasks),
                                rows=sum(len(r) for r in rows_per_part))
            with sp:
                for task in tasks:
                    assert isinstance(task, ResultTask)
                    value = task.func(iter(rows_per_part[task.partition]))
                    report(task, "success", (value, {}, {}))
        self.note_stage(stage.id, **note)
        # feed the cost model (ISSUE 7): observed device ms for this
        # program class — the other half of the device-vs-object price
        try:
            from dpark_tpu import adapt
            if adapt.enabled():
                with self._adapt_span("observe"):
                    adapt.observe_path(fuse.plan_adapt_signature(plan),
                                       "device",
                                       note["run_seconds"] * 1e3)
        except Exception:
            pass
        logger.debug("array path ran %s (%d tasks)", stage, len(tasks))
