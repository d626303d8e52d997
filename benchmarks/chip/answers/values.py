"""values: a unit returns its last output, as the step gives it (int32
values or planes); nothing is read out in the window.  Compared:
``wrong_lanes``, the lanes whose value differs from the reference."""
from generator import last, wrong_lanes

read = None     # nothing to read: the window opens no span for the answer


def keep(spec, steps, outs, got):
    return outs[last(steps)]


def compare(spec, steps, kept, env) -> dict:
    return {"wrong_lanes": wrong_lanes(kept, env[last(steps)])}


def out_bytes(spec, steps, banks: int, lanes: int) -> int:
    return banks * lanes * 4
