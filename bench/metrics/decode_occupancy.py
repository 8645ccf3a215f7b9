"""Mean live slots per decode step in the window, from the engine's
``decode_slot_steps`` / ``decode_steps`` counters (slots)."""


def read(rec):
    return rec.decode_slot_steps / rec.decode_steps if rec.decode_steps else None
