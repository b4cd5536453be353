"""Shared by the device-time readers: program time per tick."""


def per_tick_ms(rec, role: str, dev: int | None = None):
    """Device time (ms) of the programs with ``role`` on chip ``dev`` (the
    first by default) over the ticks in the traced stretch (runs of the
    ``exec`` program); None where no program has that role."""
    t = rec.trace
    if t is None or not t.devices:
        return None
    dev = min(t.devices) if dev is None else dev
    if role not in t.roles(dev).values():
        return None
    n = t.role_count(dev, "exec")
    return 1e3 * t.role_s(dev, role) / n if n else None
