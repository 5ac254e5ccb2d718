"""Input rows of all jobs completed and verified in the window, over the
window's seconds (all chips together), in millions of rows a second."""


def read(obs):
    rows = sum(j["rows"] for j in obs["jobs"] if j["ok"])
    return rows / obs["window_s"] / 1e6 if rows else None
