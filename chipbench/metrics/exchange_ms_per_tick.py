"""Device time (ms) per tick of the collective ops (the sharded plane's
exchange between chips) on the busiest chip, in the traced stretch.  None
where the trace holds no collective op, as on one chip."""
import re

# an op's name is its HLO instruction, named after the JAX primitive
# (``%all_to_all.6 = s32[4,1,128]... all-to-all(...)`` on a v5e) or after
# the HLO opcode; either names the collective
_KINDS = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
          "collective-permute", "collective-broadcast")
COLLECTIVE = re.compile(
    r"^%?(" + "|".join(k.replace("-", "[-_]") for k in _KINDS) + r")"
    r"|\b(" + "|".join(_KINDS) + r")(-start|-done)?\(")


def read(rec):
    t = rec.trace
    if t is None or not t.devices:
        return None
    best = None
    for dev in t.devices:
        s = sum(e[2] for e in t.events(dev, "XLA Ops")
                if COLLECTIVE.search(e[0])) * 1e-9
        n = t.role_count(dev, "exec")
        if s > 0 and n:
            best = max(best or 0.0, 1e3 * s / n)
    return best
