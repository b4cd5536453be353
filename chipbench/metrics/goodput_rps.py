"""Requests served correctly by the window's close, over its length."""


def read(rec):
    return rec.good_in_window / rec.window_s if rec.good_in_window else None
