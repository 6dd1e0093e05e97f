"""Mean share of decode slots occupied over the engine's ``decode`` spans
(their ``active`` count over the loop's slots; never their durations,
which cover only the enqueue)."""


def read(rec):
    occ = rec.get("decode_occupancy")
    return None if occ is None else 100.0 * occ
