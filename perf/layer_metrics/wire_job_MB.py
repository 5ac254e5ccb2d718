"""exchange: bytes the executor's all_to_all moved per job of the window
(`exchange_wire_bytes` delta)."""


def read(obs):
    wire = obs["counters"].get("exchange_wire_bytes")
    if wire is None or not obs["jobs"]:
        return None
    return wire / len(obs["jobs"]) / 1e6
