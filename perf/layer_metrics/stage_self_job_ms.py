"""driver: self time of a job's `stage.run` ring spans (a stage's
submission minus its `plan`, `join`, `stage.exec` and whatever else nests
in it: the scheduler's own work for the stage), summed, median over the
window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.self_ms(obs, "stage.run")
