"""Process start to the first timed job: imports, context start, data and
references from the seed, load to HBM, warm-up (and compilation, in a run
that compiles)."""


def read(obs):
    return obs["setup_s"]
