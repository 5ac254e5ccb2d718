"""driver: self time of a job's `job` ring span (its extent minus the
`stage.run` spans inside it: `submit_stage`, the task lists, the event
loop between stages), median over the window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.self_ms(obs, "job")
