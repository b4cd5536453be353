"""Share (%) of the traced stretch in which no op ran on the device:
1 - the union of device op intervals, averaged over the chips used."""


def read(rec):
    t = rec.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
