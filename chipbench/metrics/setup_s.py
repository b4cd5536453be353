"""Process start to the first timed request: state build on the device,
compile-cache loads (or compiles) and the warm-up traffic."""


def read(rec):
    return rec.setup_s
