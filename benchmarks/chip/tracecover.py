"""The part of a traced window that the device trace holds.

The profiler keeps a bounded number of device events a trace: 6,252,069
``XLA Ops`` events in a traced 10 s window of ``tpch-q1-sf10.q1`` on a TPU
v5e, whose programs stop 4.23 s in while the host's spans go on (a 3 s
window holds all of its 4,409,350).  A Q1 chunk runs about 40,000 device
op events, against about 430 of a scan chunk.  A share taken over the
whole window then counts the part the trace did not record as idle.

:func:`covered` cuts the window at the end of the last ``call`` span that
ended before the trace's last program did: a prefix of whole units that
the trace holds.  A trace that holds the whole window keeps all of it but
at most the time after its last unit's call.
"""
from tracereduce import Reduction


def covered(red: Reduction | None) -> Reduction | None:
    """``red`` with its window cut to the units the device trace holds."""
    if red is None or not red.modules:
        return None
    last = max(m.end for m in red.modules)
    ends = [e for n, _, e in red.spans if n == "call" and e <= last]
    if not ends:
        return None
    cut = max(ends)
    return Reduction((red.window[0], cut),
                     [(n, s, e) for n, s, e in red.spans if e <= cut],
                     [m for m in red.modules if m.start < cut])
