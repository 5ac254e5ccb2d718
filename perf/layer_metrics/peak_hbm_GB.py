"""device: memory_stats()["peak_bytes_in_use"], the fullest chip."""


def read(obs):
    peak = obs["memory_peak_bytes"]
    return peak / 1e9 if peak else None
