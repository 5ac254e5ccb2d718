"""exchange: real rows over padded slot rows that crossed the wire in the
window (`exchange_real_rows` / `exchange_slot_rows` deltas)."""


def read(obs):
    slots = obs["counters"].get("exchange_slot_rows")
    real = obs["counters"].get("exchange_real_rows")
    return 100.0 * real / slots if slots and real is not None else None
