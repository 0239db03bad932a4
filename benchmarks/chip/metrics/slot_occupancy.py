"""Share of slot-rounds in the window in which the slot decoded at least
one token, counted by the benchmark's engine proxy."""


def read(rec):
    c = rec.counts
    if not c.get("slot_rounds"):
        return None
    return 100.0 * c["active_slot_rounds"] / c["slot_rounds"]
