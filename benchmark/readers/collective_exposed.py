"""Share (%) of the traced window in which a collective ran on a device while
no other op did there, averaged over devices."""
import re

from ..xplane import merge, union_len


def read(obs, pattern):
    tr = obs.get("trace")
    if tr is None or len(tr.devices) < 2:
        return None
    rx = re.compile(pattern)
    shares = []
    for ops in tr.devices.values():
        coll = merge([(o.start, o.end) for o in ops if rx.search(o.name)])
        # leaves only: a parent (while, call) covers its children's time
        other = merge([(o.start, o.end) for o in ops
                        if not rx.search(o.name) and o.self_s >= 0.999 * (o.end - o.start)])
        exposed = union_len(coll) - _overlap(coll, other)
        shares.append(exposed / tr.window_s)
    return 100.0 * sum(shares) / len(shares)


def _overlap(a, b):
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
