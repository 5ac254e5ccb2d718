"""compile: jax compile requests inside the window (persistent-cache hits
count too: a request means a program was traced and lowered).  Expected
0."""


def read(obs):
    return obs["compiles"]["requests"]
